"""Sharding-flow analysis (difacto-lint v5): mesh/PartitionSpec
provenance through the state-carrying programs.

PRs 12-13 made the slot table mesh-sharded — contiguous fs key ranges
pinned inside every state-returning program via ``step.state_constrainer``
(``jax.lax.with_sharding_constraint``). Nothing checked those
invariants: one jit program that returns state WITHOUT the pin, or one
op that reorders or re-materializes the sharded capacity axis, silently
reintroduces the single-device memory wall the key-range sharding
exists to avoid (PAPER.md §2). This pass is
the static half of that guarantee; ``utils/hloscan.py`` (the compiled-HLO
collective/memory scan) is the runtime half and ``tools/hlomap.py`` the
merged view — the same static model + runtime tracer + tier-1
dynamic⊆static pattern as locks (v2), races (v3) and compile/transfer
flow (v4).

Two rules, both cross-file (they read the call graph + jaxflow model):

- ``jax-shard-break`` — (a) every fs-scoped jit/pjit program that
  donates state must PIN its output layout: ``out_shardings=`` on the
  jit call, a ``state_constrainer``/``with_sharding_constraint`` in the
  returned expression, or a target threaded from a pinning builder
  (``make_step_fns(..., state_shardings=...)``); (b) ops that break the
  sharded capacity axis of a table-provenance array —
  reshape/concatenate/stack/sort/boolean-mask over the table or a
  ``state.<field>`` leaf inside a state program.
- ``jax-shard-replicate`` — table-sized replication: ``device_put`` /
  ``np.asarray`` / ``jnp.asarray`` of a table-provenance array without a
  (non-replicated) sharding in fs-aware code, and donated arguments fed
  from a replicating coercion at an exact call edge (donating a fresh
  replicated copy silently forfeits the sharded in-place update).
Honest blind spots (docs/static_analysis.md v5 catalog): provenance is
lexical (scope-chain bindings, one assignment hop) — values laundered
through containers or object attributes are invisible; fs-scoping keys
on the fs-table API surface (``state_sharding`` / ``sharding_tree`` /
``state_constrainer`` / ``fs_shard_bounds`` / ``FS_AXIS``), so a mesh
program built entirely from raw ``NamedSharding`` literals is out of
scope; table provenance is name-based (``table`` / ``state.<field>``
chains). The hloscan gate exists precisely because of these holes: the
compiled HLO cannot lie about an all-gather.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .callgraph import CallGraph, get_callgraph
from .core import (Finding, Project, SourceFile, call_name, dotted,
                   enclosing_function, rule)
from .jaxflow import JitSite, get_jax_model

_FUNC_DEFS = (ast.FunctionDef, ast.AsyncFunctionDef)

# the fs-table sharding API (parallel/mesh.py + step.py): a function
# whose scope touches one of these is building or placing the fs-sharded
# table, so its jit programs are in scope for the pin check
_FS_API = {"state_sharding", "sharding_tree", "state_constrainer",
           "fs_shard_bounds", "validate_fs_capacity", "FS_AXIS"}

# the pin primitives: a returned expression passing through one of these
# carries the fs layout out of the program
_PIN_CALLS = {"state_constrainer", "with_sharding_constraint"}

# layout-threading kwargs a pinning builder accepts/forwards
_PIN_KWARGS = {"state_shardings", "mesh"}

# np/jnp calls that reorder or re-materialize the capacity axis
_AXIS_BREAKERS = {"concatenate", "stack", "append", "sort", "argsort",
                  "compress"}
_ARRAY_MODULES = {"jnp", "np", "numpy", "jax"}

# coercions that materialize their argument on one device / the host
_REPLICATORS = {"device_put", "asarray", "array"}


def _last(cn: str) -> str:
    return cn.rsplit(".", 1)[-1]


def _own_body(func) -> List[ast.AST]:
    """Nodes of ``func``'s own body, nested function/lambda bodies
    excluded — a ``return`` inside a nested def is not ``func``'s."""
    out: List[ast.AST] = []
    stack: List[ast.AST] = list(func.body)
    while stack:
        n = stack.pop()
        out.append(n)
        for c in ast.iter_child_nodes(n):
            if isinstance(c, _FUNC_DEFS + (ast.Lambda,)):
                continue
            stack.append(c)
    return out


def _scope_chain(node) -> List[ast.AST]:
    """Enclosing function defs from innermost outward (lexical scopes a
    closure or nested builder reads its bindings from)."""
    chain = []
    cur = enclosing_function(node)
    while cur is not None:
        chain.append(cur)
        cur = enclosing_function(cur)
    return chain


def _params_of(func) -> List[str]:
    a = func.args
    return [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]


def _is_table_name(name: str) -> bool:
    return name == "table"


def _table_prov(expr, local_prov: Set[str]) -> bool:
    """Name-based table provenance: the ``table`` convention
    (ops/fused.py), any ``state.<field>`` / ``store.state.<field>``
    attribute chain, or a local name assigned from one."""
    if isinstance(expr, ast.Name):
        return _is_table_name(expr.id) or expr.id in local_prov
    if isinstance(expr, ast.Attribute):
        segs = dotted(expr).split(".")
        return len(segs) > 1 and "state" in segs[:-1] \
            or _is_table_name(segs[-1])
    return False


class ShardModel:
    """The whole-program sharding-flow model. Built once per Project
    (cached — the three rules, hlomap, and the tier-1 gate share it)."""

    def __init__(self, project: Project):
        self.project = project
        self.cg: CallGraph = get_callgraph(project)
        self.jax = get_jax_model(project)
        self._findings: Dict[str, List[Finding]] = {
            "jax-shard-break": [], "jax-shard-replicate": []}
        self._fn_pins_memo: Dict[int, bool] = {}
        self.pinning_builders: Set[str] = set()       # bare def names
        self.state_programs: Dict[str, dict] = {}     # site_id -> verdict
        self._find_pinning_builders()
        self._check_state_programs()
        self._check_axis_breaks()
        self._check_replication()

    # ------------------------------------------------- pinning builders
    def _find_pinning_builders(self) -> None:
        """Fixpoint over bare def names: a builder pins when it accepts
        a layout kwarg (``state_shardings``/``mesh``) and reaches a
        ``state_constrainer``/``with_sharding_constraint`` call, either
        directly or by forwarding the kwarg into another pinning
        builder (a wrapper around ``step.make_step_fns``)."""
        defs: Dict[str, List[ast.AST]] = {}
        for sf in self._sources():
            for n in sf.walk():
                if isinstance(n, _FUNC_DEFS):
                    defs.setdefault(n.name, []).append(n)
        self._defs_by_name = defs

        def accepts_layout(func) -> bool:
            return bool(_PIN_KWARGS & set(_params_of(func)))

        names = set()
        for name, nodes in defs.items():
            for func in nodes:
                if not accepts_layout(func):
                    continue
                if any(isinstance(n, ast.Call)
                       and _last(call_name(n)) in _PIN_CALLS
                       for n in ast.walk(func)):
                    names.add(name)
        changed = True
        while changed:
            changed = False
            for name, nodes in defs.items():
                if name in names:
                    continue
                for func in nodes:
                    if not accepts_layout(func):
                        continue
                    for n in ast.walk(func):
                        if isinstance(n, ast.Call) \
                                and _last(call_name(n)) in names \
                                and any(kw.arg in _PIN_KWARGS
                                        for kw in n.keywords):
                            names.add(name)
                            changed = True
                            break
                    if name in names:
                        break
        self.pinning_builders = names

    # ------------------------------------------------ rule 1a: the pin
    def _sources(self):
        for sf in self.project.files:
            if sf.tree is not None \
                    and not sf.rel.endswith("utils/jaxtrace.py"):
                yield sf

    def _fs_aware(self, scope) -> bool:
        for n in ast.walk(scope):
            if isinstance(n, ast.Name) and n.id in _FS_API:
                return True
            if isinstance(n, ast.Attribute) and n.attr in _FS_API:
                return True
        return False

    def _constrain_names(self, node) -> Set[str]:
        """Names bound from ``state_constrainer(...)`` in the lexical
        scope chain of ``node`` (the ``constrain = state_constrainer(
        shardings)`` convention)."""
        out: Set[str] = set()
        for scope in _scope_chain(node):
            for n in _own_body(scope):
                if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                        and isinstance(n.targets[0], ast.Name) \
                        and isinstance(n.value, ast.Call) \
                        and _last(call_name(n.value)) == \
                        "state_constrainer":
                    out.add(n.targets[0].id)
        return out

    def _binding_of(self, node, name: str):
        """(rhs_call, elem_index) when ``name`` is bound — directly or
        by tuple-unpack — from a Call in the lexical scope chain of
        ``node``; (None, None) otherwise."""
        for scope in _scope_chain(node):
            for n in _own_body(scope):
                if not isinstance(n, ast.Assign) \
                        or not isinstance(n.value, ast.Call):
                    continue
                for t in n.targets:
                    if isinstance(t, ast.Name) and t.id == name:
                        return n.value, None
                    if isinstance(t, (ast.Tuple, ast.List)):
                        for i, el in enumerate(t.elts):
                            if isinstance(el, ast.Name) and el.id == name:
                                return n.value, i
        return None, None

    def _pinning_call(self, call: ast.Call) -> bool:
        """A call that yields pinned programs: a pinning builder invoked
        WITH the layout kwarg threaded, or a pin primitive itself."""
        cn = _last(call_name(call))
        if cn in _PIN_CALLS:
            return True
        return cn in self.pinning_builders \
            and any(kw.arg in _PIN_KWARGS for kw in call.keywords)

    def _expr_pins(self, expr, anchor, constrain: Set[str]) -> bool:
        """Does ``expr`` (a returned value) pass state through a pin?
        True when it contains a call to a pin primitive, to a
        constrain-bound name, to a pinned local def, or to a name bound
        from a pinning-builder call."""
        for n in ast.walk(expr):
            if not isinstance(n, ast.Call):
                continue
            cn = call_name(n)
            if _last(cn) in _PIN_CALLS:
                return True
            if isinstance(n.func, ast.Name):
                nm = n.func.id
                if nm in constrain:
                    return True
                local = self._local_def(anchor, nm)
                if local is not None and self._fn_pins(local):
                    return True
                bcall, _ = self._binding_of(anchor, nm)
                if bcall is not None and self._pinning_call(bcall):
                    return True
        return False

    def _local_def(self, anchor, name: str):
        for scope in _scope_chain(anchor):
            for n in _own_body(scope):
                if isinstance(n, _FUNC_DEFS) and n.name == name:
                    return n
        return None

    def _fn_pins(self, func) -> bool:
        """A function pins when every path that can return state passes
        it through a pin: some returned expression contains a pinning
        call, or a returned name is bound from one."""
        memo = self._fn_pins_memo
        if id(func) in memo:
            return memo[id(func)]
        memo[id(func)] = False       # cycle guard: assume unpinned
        constrain = self._constrain_names(func) \
            | self._constrain_names_in(func)
        pinned = False
        for n in _own_body(func):
            if not isinstance(n, ast.Return) or n.value is None:
                continue
            if self._expr_pins(n.value, func, constrain):
                pinned = True
                break
            names = []
            if isinstance(n.value, ast.Name):
                names = [n.value.id]
            elif isinstance(n.value, ast.Tuple):
                names = [e.id for e in n.value.elts
                         if isinstance(e, ast.Name)]
            for nm in names:
                bcall = self._body_binding(func, nm)
                if bcall is not None and (
                        self._pinning_call(bcall)
                        or self._call_pins(bcall, func, constrain)):
                    pinned = True
                    break
            if pinned:
                break
        memo[id(func)] = pinned
        return pinned

    def _constrain_names_in(self, func) -> Set[str]:
        out: Set[str] = set()
        for n in _own_body(func):
            if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                    and isinstance(n.targets[0], ast.Name) \
                    and isinstance(n.value, ast.Call) \
                    and _last(call_name(n.value)) == "state_constrainer":
                out.add(n.targets[0].id)
        return out

    def _body_binding(self, func, name: str) -> Optional[ast.Call]:
        for n in _own_body(func):
            if not isinstance(n, ast.Assign) \
                    or not isinstance(n.value, ast.Call):
                continue
            for t in n.targets:
                if isinstance(t, ast.Name) and t.id == name:
                    return n.value
                if isinstance(t, (ast.Tuple, ast.List)) and any(
                        isinstance(e, ast.Name) and e.id == name
                        for e in t.elts):
                    return n.value
        return None

    def _call_pins(self, call: ast.Call, anchor, constrain: Set[str]
                   ) -> bool:
        """Does the value of ``call`` come out pinned? A call to a
        constrain-bound name, a pinned local def, or a name bound from
        a pinning-builder call."""
        if not isinstance(call.func, ast.Name):
            return False
        nm = call.func.id
        if nm in constrain:
            return True
        local = self._local_def(anchor, nm)
        if local is not None and self._fn_pins(local):
            return True
        bcall, _ = self._binding_of(anchor, nm)
        return bcall is not None and self._pinning_call(bcall)

    def _site_pinned(self, site: JitSite) -> Tuple[bool, str]:
        node = site.node
        if isinstance(node, ast.Call) and any(
                kw.arg == "out_shardings" for kw in node.keywords):
            return True, "out_shardings"
        constrain = self._constrain_names(node)
        t = site.target_node
        if isinstance(t, ast.Lambda):
            return (self._expr_pins(t.body, node, constrain), "lambda")
        if isinstance(t, _FUNC_DEFS):
            return (self._fn_pins(t), "target")
        # jit over a bare name the jaxflow pass could not resolve to a
        # def: a local binding from a builder call (the
        # `_, train_step, _ = make_step_fns(..., state_shardings=...)`
        # convention)
        if site.target_name not in ("<unknown>", "<lambda>"):
            bcall, _ = self._binding_of(node, site.target_name)
            if bcall is not None:
                return (self._pinning_call(bcall), "builder")
        return False, "unresolved"

    def _check_state_programs(self) -> None:
        for sid, site in sorted(self.jax.sites.items()):
            if not site.donates:
                continue
            scope = enclosing_function(site.node) or site.sf.tree
            if not self._fs_aware(scope):
                continue
            pinned, how = self._site_pinned(site)
            self.state_programs[sid] = {
                "target": site.target_name, "pinned": pinned, "pin": how,
                "donate_argnums": list(site.donates)}
            if not pinned:
                self._findings["jax-shard-break"].append(site.sf.finding(
                    "jax-shard-break", site.node,
                    f"jit program `{site.target_name}` donates state in "
                    f"fs-aware code but never pins its output layout — "
                    f"thread state_shardings through the step builder "
                    f"(step.state_constrainer) or pass out_shardings=, "
                    f"else GSPMD inference may re-partition or replicate "
                    f"the table and break the donated in-place update"))

    # --------------------------------------------- rule 1b: axis breaks
    def _state_scoped_funcs(self):
        """Functions in the state-program convention: a parameter named
        ``state`` or ``table`` (the step/updater/kernel surfaces the
        sharded arrays flow through)."""
        for sf in self._sources():
            for n in sf.walk():
                if isinstance(n, _FUNC_DEFS):
                    params = set(_params_of(n))
                    if "state" in params or "table" in params:
                        yield sf, n

    def _local_prov(self, func) -> Set[str]:
        """One assignment hop: names bound from a table-provenance
        expression inside ``func``."""
        out: Set[str] = set()
        for n in _own_body(func):
            if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                    and isinstance(n.targets[0], ast.Name) \
                    and _table_prov(n.value, out):
                out.add(n.targets[0].id)
        return out

    def _check_axis_breaks(self) -> None:
        for sf, func in self._state_scoped_funcs():
            prov = self._local_prov(func)
            for n in _own_body(func):
                if isinstance(n, ast.Call):
                    self._axis_break_call(sf, func, n, prov)
                elif isinstance(n, ast.Subscript) \
                        and _table_prov(n.value, prov) \
                        and isinstance(n.slice, ast.Compare):
                    self._findings["jax-shard-break"].append(sf.finding(
                        "jax-shard-break", n,
                        f"boolean mask over the capacity axis of "
                        f"`{dotted(n.value)}` — a data-dependent shape "
                        f"over the fs-sharded table axis forces a "
                        f"re-materialized (replicated) table; gather "
                        f"with a padded slot vector instead"))

    def _axis_break_call(self, sf: SourceFile, func, call: ast.Call,
                         prov: Set[str]) -> None:
        cn = call_name(call)
        seg = _last(cn)
        # method-form reshape on a table value: state.w.reshape(...)
        if isinstance(call.func, ast.Attribute) \
                and call.func.attr == "reshape" \
                and _table_prov(call.func.value, prov):
            self._findings["jax-shard-break"].append(sf.finding(
                "jax-shard-break", call,
                f"`{dotted(call.func.value)}.reshape(...)` re-lays-out "
                f"the fs-sharded capacity axis — reshapes across the "
                f"table's axis 0 force GSPMD to re-materialize the "
                f"table; keep the capacity axis intact"))
            return
        if "." not in cn or cn.split(".", 1)[0] not in _ARRAY_MODULES:
            return
        if seg == "reshape" and call.args \
                and _table_prov(call.args[0], prov):
            self._findings["jax-shard-break"].append(sf.finding(
                "jax-shard-break", call,
                f"`{cn}` over a table-provenance array re-lays-out the "
                f"fs-sharded capacity axis; keep axis 0 intact"))
            return
        if seg not in _AXIS_BREAKERS or not call.args:
            return
        a0 = call.args[0]
        operands = a0.elts if isinstance(a0, (ast.Tuple, ast.List)) \
            else [a0]
        if not any(_table_prov(op, prov) for op in operands):
            return
        self._findings["jax-shard-break"].append(sf.finding(
            "jax-shard-break", call,
            f"`{cn}` over a table-provenance array breaks the sharded "
            f"capacity axis (axis 0 is the fs key-range dimension — "
            f"reordering or growing it on device re-materializes the "
            f"table across shards); do this on per-shard host views "
            f"(fs_shard_bounds) or on gathered rows, not the table"))

    # ------------------------------------------- rule 2: replication
    def _replicating_call(self, call: ast.Call) -> Optional[str]:
        """Why ``call`` replicates its argument, or None. device_put
        with no placement (or an explicit ``replicated(...)``) lands the
        whole array on one layout; np/jnp asarray materializes it."""
        cn = call_name(call)
        seg = _last(cn)
        if seg == "device_put":
            if len(call.args) < 2 and not call.keywords:
                return "device_put with no sharding"
            placements = list(call.args[1:]) + [
                kw.value for kw in call.keywords]
            for p in placements:
                if isinstance(p, ast.Call) \
                        and _last(call_name(p)) == "replicated":
                    return "device_put(..., replicated(mesh))"
            return None
        if seg in ("asarray", "array") and "." in cn \
                and cn.split(".", 1)[0] in ("np", "numpy", "jnp"):
            return f"{cn} materializes the full table on host/one device"
        if seg == "fetch" and "jaxtrace" in cn:
            return "jaxtrace.fetch pulls the full table to host"
        return None

    def _check_replication(self) -> None:
        # (a) table-provenance arrays re-placed in fs-aware functions
        for sf in self._sources():
            for n in sf.walk():
                if not isinstance(n, _FUNC_DEFS):
                    continue
                if not self._fs_aware(n):
                    continue
                prov = self._local_prov(n)
                for c in _own_body(n):
                    if not isinstance(c, ast.Call) or not c.args:
                        continue
                    why = self._replicating_call(c)
                    if why and _table_prov(c.args[0], prov):
                        self._findings["jax-shard-replicate"].append(
                            sf.finding(
                                "jax-shard-replicate", c,
                                f"table-sized replication: {why} — the "
                                f"fs-sharded table must move through "
                                f"put_global/shard_pytree with its "
                                f"state_sharding spec, never through a "
                                f"replicated or host copy (that is the "
                                f"single-device memory wall fs-sharding "
                                f"removes)"))
        # (b) donated arguments fed from a replicating coercion at the
        # exact call edges of the fs-scoped state programs
        for sid in sorted(self.state_programs):
            site = self.jax.sites[sid]
            for cs in site.call_sites:
                for d in site.donates:
                    if d >= len(cs.args):
                        continue
                    arg = cs.args[d]
                    why = None
                    if isinstance(arg, ast.Call):
                        why = self._replicating_call(arg)
                    elif isinstance(arg, ast.Name):
                        bcall, _ = self._binding_of(cs, arg.id)
                        if bcall is not None:
                            why = self._replicating_call(bcall)
                    if why:
                        csf = self._sf_of(cs, site)
                        self._findings["jax-shard-replicate"].append(
                            csf.finding(
                                "jax-shard-replicate", cs,
                                f"donated argument {d} of "
                                f"`{site.target_name}` is fed from a "
                                f"replicating coercion ({why}) — the "
                                f"donated state must arrive under its "
                                f"fs sharding or the in-place table "
                                f"update degrades to a full copy"))

    def _sf_of(self, node, site: JitSite) -> SourceFile:
        for sf in self.project.files:
            if sf.tree is not None and node in sf.walk():
                return sf
        return site.sf

    # ----------------------------------------------------------- views
    def to_json(self) -> dict:
        """The static model hlomap and the tier-1 gate consume: the
        fs-scoped state programs with their pin verdicts and the full
        jit-site universe (dynamic hloscan sites must be a subset)."""
        return {
            "state_programs": {sid: dict(rec) for sid, rec in
                               sorted(self.state_programs.items())},
            "pinning_builders": sorted(self.pinning_builders),
            "sites": sorted(self.jax.sites),
        }


def get_shard_model(project: Project) -> ShardModel:
    m = getattr(project, "_shard_model", None)
    if m is None or m.project is not project:
        m = ShardModel(project)
        project._shard_model = m  # type: ignore[attr-defined]
    return m


# ---------------------------------------------------------------------------
# rule registrations


@rule("jax-shard-break",
      "fs-scoped state programs must pin their output layout; no ops "
      "that break the sharded capacity axis", cross=True)
def check_jax_shard_break(project: Project) -> List[Finding]:
    return list(get_shard_model(project)._findings["jax-shard-break"])


@rule("jax-shard-replicate",
      "no table-sized replication: the fs-sharded table never moves "
      "through a replicated or host copy", cross=True)
def check_jax_shard_replicate(project: Project) -> List[Finding]:
    return list(
        get_shard_model(project)._findings["jax-shard-replicate"])
