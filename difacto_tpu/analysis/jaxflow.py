"""JAX compile/transfer flow analysis (difacto-lint v4).

The tree's two core JAX invariants — "zero steady-state recompiles" on
the serve path and donated in-place slot updates with byte-identical
trajectories — are compile-cache and aliasing properties that the
earlier JAX rules (jax-donate / jax-jit-capture / jax-host-call in
localrules.py) only check one function at a time. This pass is
whole-program: it discovers every jit program in the tree, follows its
call sites through the shared call graph (callgraph.py), and checks
four property families:

- **jax-recompile** — the compile-key model. Every value feeding a
  ``static_argnums`` position at a wrapper call site must be provably
  drawn from a BOUNDED set: constants, config-derived fields
  (``*.param.*``), sticky shape caps (``ShapeSchedule.cap``) and
  bucket rungs (``ops.batch.bucket``), attributes only ever assigned
  from bounded values, and parameters whose every exact caller passes
  bounded values (a depth-capped fixpoint). A static fed straight from
  data (``len(...)``, ``.size``/``.nnz``/``.shape``) compiles a new
  program per distinct value — the exact hazard the executor's bucket
  caps exist to prevent. Also flagged: a jit wrapper built inside a
  loop or invoked immediately (``jit(f)(x)`` — a fresh compile-cache
  entry per call), and non-hashable literals at static positions
  (a ``TypeError`` at trace time).

- **jax-host-sync** — implicit device->host syncs on the hot path,
  interprocedurally. Results of jitted wrappers are *device values*;
  coercing one on the host (``float()``/``int()``/``bool()``/
  ``np.asarray``/``.item()``/``.tolist()``/``print``) blocks on the
  device pipeline. Inside the hot step/dispatch loops (any function
  that calls a jit wrapper from inside a loop, every ``*._loop``, and
  everything they reach over exact call edges) such a coercion must be
  a DECLARED sync: ``utils.jaxtrace.fetch(x)`` — which the runtime
  tracer counts — or carry a reasoned suppression. Taint flows through
  local assignment, tuple unpacking, helper parameters, and helper
  returns (one fixpoint over the hot set).

- **jax-donate-flow** — donation declarations that cannot work:
  a donated index that is also a static (never a buffer), a donated
  index past the target's positional parameters, the same name passed
  at a donated AND a non-donated position of one call (the aliased
  read is undefined), and the cross-edge read-after-donate the local
  jax-donate rule cannot see: the donated argument is the enclosing
  function's parameter, and an exact CALLER keeps reading the buffer
  it passed after the call returns.

- **jax-dtype64** (local) — dtype drift into the fp32 device pipeline:
  ``float64`` mentions inside jit targets (a single np.float64
  intermediate promotes the whole computation), ``dtype=float64`` on
  ``jnp`` device-array creation anywhere, and int32 accumulators
  (``x += ...`` in a loop on an int32-created counter) on paths that
  can overflow past 2^31 rows. Host-side float64 OUTSIDE jit targets
  is deliberate in this tree (exact text parsing, DCN reduction wires,
  the two-loop solver) and is not flagged.

The runtime complement is ``utils/jaxtrace.py`` (``DIFACTO_JAXTRACE=1``)
whose per-site compile counts and fetch counts the tier-1 gate
(tests/test_jaxflow.py) checks against this model: observed jit sites
must be statically known and warm-declared, steady-state compiles must
stop growing, and observed transfers must be declared fetch points.
``tools/jitmap.py`` renders the merged view (``make jitmap``).
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from .callgraph import CallGraph, get_callgraph
from .core import (Finding, Project, SourceFile, call_name, dotted,
                   enclosing_function, node_key, rule, statement_of)

_FUNC_DEFS = (ast.FunctionDef, ast.AsyncFunctionDef)
_LOOPS = (ast.For, ast.AsyncFor, ast.While)

# calls that quantize a data-dependent value onto a bounded set: the
# sticky shape caps (data/pack_stream.ShapeSchedule.cap and .row_cap,
# only grow, log-many values) and the rungs (ops/batch.bucket, row_cap)
_BOUNDING_CALLS = {"cap", "bucket", "row_cap"}
# attribute segments that mark config-derived constants (difacto's
# Param dataclasses): bounded for a run's lifetime
_CONFIG_SEGMENTS = {"param", "uparam"}
# data-dependent attributes: feeding one of these to a static position
# is the canonical recompile hazard
_DATA_ATTRS = {"size", "nnz", "shape", "ndim"}
_COERCIONS = {"float", "int", "bool"}
_NP_SINKS = {"asarray", "array"}
_ITEM_SINKS = {"item", "tolist"}

_PROV_DEPTH = 8


def _self_shift(func, fi) -> int:
    """1 when callers' positional args are offset by an implicit
    receiver: the function is a METHOD (first parameter self/cls AND
    the callgraph places it in a class). Nested functions inside a
    method keep the class context but take no receiver."""
    if fi is None or fi.cls is None or not isinstance(func, _FUNC_DEFS):
        return 0
    params = func.args.posonlyargs + func.args.args
    return 1 if params and params[0].arg in ("self", "cls") else 0


def _is_fetch_call(cn: str) -> bool:
    """Only the tracer's own ``jaxtrace.fetch`` is the declared sync —
    other ``.fetch`` methods in the tree (tile caches) move device
    data and must NOT sanction or untaint anything."""
    return cn == "jaxtrace.fetch" or cn.endswith(".jaxtrace.fetch")


def _is_jit_name(cn: str) -> bool:
    return cn in ("jit", "pjit") or cn.endswith(".jit") \
        or cn.endswith(".pjit")


def _jit_call_parts(call: ast.Call):
    """(is_jit, keywords) for a ``jit(...)`` / ``partial(jit, ...)``
    call — the partial form carries the jit kwargs on the partial."""
    cn = call_name(call)
    if _is_jit_name(cn):
        return True, call.keywords
    if (cn == "partial" or cn.endswith(".partial")) and call.args:
        an = dotted(call.args[0])
        if _is_jit_name(an):
            return True, call.keywords
    return False, []


def _int_tuple(kwval) -> Tuple[int, ...]:
    consts = kwval.elts if isinstance(kwval, (ast.Tuple, ast.List)) \
        else [kwval]
    return tuple(c.value for c in consts
                 if isinstance(c, ast.Constant) and isinstance(c.value, int))


@dataclass
class JitSite:
    site_id: str                    # "rel:lineno" — jaxtrace identity
    sf: SourceFile
    node: ast.AST                   # the jit call / decorator node
    bound: Optional[str]            # node_key of the bound name, or None
    target_name: str                # wrapped function's name (or <lambda>)
    target_node: Optional[ast.AST]  # FunctionDef / Lambda when resolvable
    statics: Tuple[int, ...] = ()
    donates: Tuple[int, ...] = ()
    owner: str = ""                 # qual of the function holding the jit()
    call_sites: List[ast.Call] = field(default_factory=list)
    unbounded: List[Tuple[ast.Call, int, str]] = field(default_factory=list)

    @property
    def bounded(self) -> bool:
        return not self.unbounded


class JaxModel:
    """The whole-program jit/transfer model. Built once per Project
    (cached — all four rules, jitmap, and the tier-1 gate share it)."""

    def __init__(self, project: Project):
        self.project = project
        self.cg: CallGraph = get_callgraph(project)
        self.sites: Dict[str, JitSite] = {}
        self.fetch_sites: Dict[str, int] = {}    # "rel:lineno" -> lineno
        self.hot_funcs: Set[str] = set()
        self.hot_roots: Set[str] = set()
        self._call_to_site: Dict[int, JitSite] = {}
        self._findings: Dict[str, List[Finding]] = {
            "jax-recompile": [], "jax-host-sync": [], "jax-donate-flow": []}
        self._bounded_memo: Dict[Tuple[str, str], Optional[str]] = {}
        self._attr_inprog: Set[Tuple[str, str]] = set()
        for sf in project.files:
            # the tracer module itself wraps jax.jit — it is the
            # instrument, not a program of the tree
            if sf.tree is not None \
                    and not sf.rel.endswith("utils/jaxtrace.py"):
                self._discover_sites(sf)
        self._index_call_sites()
        self._discover_hot()
        self._check_recompile()
        self._check_host_sync()
        self._check_donate_flow()

    # -------------------------------------------------------- discovery
    def _discover_sites(self, sf: SourceFile) -> None:
        # jit(...) calls (incl. jaxtrace.jit and partial(jax.jit, ...))
        for call in sf.call_nodes():
            is_jit, kws = _jit_call_parts(call)
            if not is_jit:
                continue
            if isinstance(getattr(call, "parent", None), ast.Call) \
                    and call.parent.func is call:  # type: ignore
                pass   # jit(f)(x): recorded below, still model the site
            cn = call_name(call)
            target = None
            tname = "<unknown>"
            args = call.args
            if cn == "partial" or cn.endswith(".partial"):
                args = call.args[1:]
            if args:
                a0 = args[0]
                if isinstance(a0, ast.Lambda):
                    target, tname = a0, "<lambda>"
                elif isinstance(a0, ast.Name):
                    tname = a0.id
                    target = self._find_def(sf, call, a0.id)
                elif isinstance(a0, ast.Attribute):
                    tname = dotted(a0)
            statics: Tuple[int, ...] = ()
            donates: Tuple[int, ...] = ()
            for kw in kws:
                if kw.arg == "static_argnums":
                    statics = _int_tuple(kw.value)
                elif kw.arg == "donate_argnums":
                    donates = _int_tuple(kw.value)
            # a decorator-position partial(jit, ...) wraps the def below
            parent = getattr(call, "parent", None)
            if isinstance(parent, _FUNC_DEFS) \
                    and call in parent.decorator_list:
                target, tname = parent, parent.name
                bound = parent.name
            else:
                bound = self._bound_key(call)
                if args and target is None and tname == "<unknown>":
                    pass
            owner = self.cg.owner_of.get(id(call), sf.rel + "::<module>")
            site = JitSite(f"{sf.rel}:{call.lineno}", sf, call, bound,
                           tname, target, statics, donates, owner)
            self.sites[site.site_id] = site
            self._call_to_site[id(call)] = site
        # bare @jit / @mod.jit decorators (no Call node)
        for node in sf.walk():
            if not isinstance(node, _FUNC_DEFS):
                continue
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call):
                    continue       # handled above via call discovery
                dn = dotted(dec)
                if dn and _is_jit_name(dn):
                    owner = self.cg.owner_of.get(
                        id(node), sf.rel + "::<module>")
                    site = JitSite(f"{sf.rel}:{dec.lineno}", sf, dec,
                                   node.name, node.name, node,
                                   owner=owner)
                    self.sites[site.site_id] = site
        # declared sync points: utils.jaxtrace.fetch(...)
        for call in sf.call_nodes():
            if _is_fetch_call(call_name(call)):
                self.fetch_sites[f"{sf.rel}:{call.lineno}"] = call.lineno

    def _find_def(self, sf: SourceFile, call: ast.Call, name: str):
        """The FunctionDef a jit() wraps, searched lexically: nested
        defs of the enclosing function first, then module level."""
        scope = enclosing_function(call) or sf.tree
        for n in ast.walk(scope):
            if isinstance(n, _FUNC_DEFS) and n.name == name:
                return n
        for n in sf.walk():
            if isinstance(n, _FUNC_DEFS) and n.name == name:
                return n
        return None

    def _bound_key(self, call: ast.Call) -> Optional[str]:
        stmt = statement_of(call)
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and stmt.value is call:
            return node_key(stmt.targets[0]) or None
        if isinstance(stmt, ast.AnnAssign) and stmt.value is call:
            return node_key(stmt.target) or None
        return None

    def _index_call_sites(self) -> None:
        """Wrapper call sites, matched per file by the bound key
        (``self._packed(...)`` matches the ``._packed = jit(...)``
        binding whatever the receiver half — the node_key contract the
        local jax rules already use) or, for decorated defs, by the
        call graph's exact resolution."""
        by_file: Dict[str, List[JitSite]] = {}
        decorated: Dict[str, JitSite] = {}
        for site in self.sites.values():
            if isinstance(site.target_node, _FUNC_DEFS) \
                    and site.bound == site.target_name:
                qual = self.cg._def_qual.get(id(site.target_node))
                if qual:
                    decorated[qual] = site
            if site.bound:
                by_file.setdefault(site.sf.rel, []).append(site)
        for sf in self.project.files:
            if sf.tree is None:
                continue
            sites = by_file.get(sf.rel, [])
            keys = {s.bound: s for s in sites}
            for call in sf.call_nodes():
                if id(call) in self._call_to_site:
                    continue
                k = node_key(call.func)
                site = keys.get(k)
                if site is not None:
                    site.call_sites.append(call)
        for qual, site in decorated.items():
            for caller, csites in self.cg.calls.items():
                for cs in csites:
                    if cs.kind == "call" and not cs.fuzzy \
                            and qual in cs.targets \
                            and id(cs.node) not in self._call_to_site:
                        if cs.node not in site.call_sites:
                            site.call_sites.append(cs.node)

    # ---------------------------------------------------------- hot set
    def _discover_hot(self) -> None:
        """Hot roots: every function that dispatches a jit wrapper from
        inside a loop (a step/replay loop), plus every ``_loop`` (the
        serve dispatch threads). The hot set is their closure over
        exact call edges — where an implicit sync stalls the pipeline
        every iteration, not once."""
        wrapper_calls: Dict[str, List[ast.Call]] = {}
        for site in self.sites.values():
            for c in site.call_sites:
                owner = self.cg.owner_of.get(id(c))
                if owner:
                    wrapper_calls.setdefault(owner, []).append(c)
        # functions that (transitively, over exact edges) invoke a jit
        # wrapper: a loop that calls one of these dispatches device work
        # every iteration even when the jit call itself lives in a
        # helper (_dispatch_packed and friends)
        invokes: Set[str] = set(wrapper_calls)
        changed = True
        while changed:
            changed = False
            for qual, csites in self.cg.calls.items():
                if qual in invokes:
                    continue
                for cs in csites:
                    if cs.kind == "call" and not cs.fuzzy \
                            and any(t in invokes for t in cs.targets):
                        invokes.add(qual)
                        changed = True
                        break
        wrapper_ids = {id(c) for calls in wrapper_calls.values()
                       for c in calls}
        for qual, csites in self.cg.calls.items():
            for cs in csites:
                dispatches = id(cs.node) in wrapper_ids \
                    or (cs.kind == "call" and not cs.fuzzy
                        and any(t in invokes for t in cs.targets))
                if not dispatches:
                    continue
                cur = getattr(cs.node, "parent", None)
                while cur is not None and not isinstance(cur, _FUNC_DEFS):
                    if isinstance(cur, _LOOPS):
                        self.hot_roots.add(qual)
                        break
                    cur = getattr(cur, "parent", None)
        for qual, fi in self.cg.funcs.items():
            if fi.name == "_loop":
                self.hot_roots.add(qual)
        seen = set(self.hot_roots)
        frontier = list(seen)
        while frontier:
            q = frontier.pop()
            for cs in self.cg.calls.get(q, []):
                if cs.kind != "call" or cs.fuzzy:
                    continue
                for t in cs.targets:
                    if t not in seen and t in self.cg.funcs:
                        seen.add(t)
                        frontier.append(t)
        self.hot_funcs = seen

    # ----------------------------------------------- bounded provenance
    def _bounded(self, sf: SourceFile, func, expr,
                 depth: int = 0) -> Optional[str]:
        """None when ``expr`` is provably drawn from a bounded set;
        otherwise a human-readable reason naming the unbounded source."""
        if depth > _PROV_DEPTH:
            return "provenance chain too deep"
        if isinstance(expr, ast.Constant):
            return None
        if isinstance(expr, (ast.Tuple, ast.List)):
            for e in expr.elts:
                r = self._bounded(sf, func, e, depth + 1)
                if r:
                    return r
            return None
        if isinstance(expr, ast.UnaryOp):
            return self._bounded(sf, func, expr.operand, depth + 1)
        if isinstance(expr, ast.BinOp):
            return self._bounded(sf, func, expr.left, depth + 1) \
                or self._bounded(sf, func, expr.right, depth + 1)
        if isinstance(expr, (ast.BoolOp,)):
            for e in expr.values:
                r = self._bounded(sf, func, e, depth + 1)
                if r:
                    return r
            return None
        if isinstance(expr, ast.Compare):
            return None                     # a bool: two values
        if isinstance(expr, ast.IfExp):
            return self._bounded(sf, func, expr.body, depth + 1) \
                or self._bounded(sf, func, expr.orelse, depth + 1)
        if isinstance(expr, ast.Call):
            cn = call_name(expr)
            tail = cn.rsplit(".", 1)[-1]
            if tail in _BOUNDING_CALLS:
                return None                 # cap()/bucket(): quantized
            if tail == "len":
                return "len(...) is data-dependent"
            if tail in ("int", "min", "max", "abs", "round"):
                for e in expr.args:
                    r = self._bounded(sf, func, e, depth + 1)
                    if r:
                        return r
                return None
            if tail == "bool":
                return None
            return f"value of {cn or '<dynamic>'}(...) not provably bounded"
        if isinstance(expr, ast.Attribute):
            chain = dotted(expr)
            parts = chain.split(".") if chain else []
            if any(p in _CONFIG_SEGMENTS for p in parts[:-1]):
                return None                 # config-derived constant
            if expr.attr in _DATA_ATTRS:
                return f"`.{expr.attr}` is data-dependent — route it " \
                       f"through a ShapeSchedule cap or bucket rung"
            return self._attr_bounded(sf, expr.attr, depth)
        if isinstance(expr, ast.Name):
            return self._name_bounded(sf, func, expr.id, depth)
        if isinstance(expr, ast.Subscript):
            return "subscripted value (payload/tuple element) not " \
                   "provably bounded"
        if isinstance(expr, ast.Starred):
            return self._bounded(sf, func, expr.value, depth + 1)
        return f"{type(expr).__name__} expression not provably bounded"

    def _attr_bounded(self, sf: SourceFile, attr: str,
                      depth: int) -> Optional[str]:
        """``<obj>.attr`` is bounded when every assignment to ``.attr``
        in the same file has a bounded RHS (and at least one exists —
        an attribute this file never sets is somebody else's data)."""
        memo_key = (sf.rel, "." + attr)
        if memo_key in self._bounded_memo:
            return self._bounded_memo[memo_key]
        if memo_key in self._attr_inprog:
            return None                     # optimistic on cycles
        self._attr_inprog.add(memo_key)
        try:
            stores = []
            for node in sf.walk():
                if isinstance(node, ast.Assign) \
                        and len(node.targets) == 1 \
                        and node_key(node.targets[0]) == "." + attr:
                    stores.append(node)
                elif isinstance(node, ast.AnnAssign) \
                        and node_key(node.target) == "." + attr \
                        and node.value is not None:
                    stores.append(node)
            if not stores:
                res: Optional[str] = \
                    f"`.{attr}` is never assigned in {sf.rel} — " \
                    f"not provably bounded"
            else:
                res = None
                for st in stores:
                    f = enclosing_function(st)
                    res = self._bounded(sf, f, st.value, depth + 1)
                    if res:
                        res = f"`.{attr}` assigned from an unbounded " \
                              f"value at {sf.rel}:{st.lineno} ({res})"
                        break
            self._bounded_memo[memo_key] = res
            return res
        finally:
            self._attr_inprog.discard(memo_key)

    def _name_bounded(self, sf: SourceFile, func, name: str,
                      depth: int) -> Optional[str]:
        # local / enclosing assignments first; a tuple-unpack target
        # remembers its POSITION so `(a, b) = payload` can check just
        # the matching element of the caller's literal payload tuple
        scope = func if func is not None else sf.tree
        # self-referential rebinding (`u_cap = max(u_cap, bucket(n))`)
        # recurses through itself: optimistic on cycles — the base
        # binding and every step still get checked on their own
        cyc_key = (f"name@{id(scope)}", name)
        if cyc_key in self._attr_inprog:
            return None
        self._attr_inprog.add(cyc_key)
        try:
            return self._name_bounded_inner(sf, func, scope, name, depth)
        finally:
            self._attr_inprog.discard(cyc_key)

    def _name_bounded_inner(self, sf: SourceFile, func, scope, name: str,
                            depth: int) -> Optional[str]:
        assigns: List[Tuple[ast.AST, Optional[int]]] = []
        for node in ast.walk(scope):
            if isinstance(node, _FUNC_DEFS) and node is not scope:
                continue
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id == name:
                        assigns.append((node.value, None))
                    elif isinstance(t, ast.Tuple):
                        for pos, e in enumerate(t.elts):
                            if isinstance(e, ast.Name) and e.id == name:
                                assigns.append((node.value, pos))
            elif isinstance(node, ast.AugAssign) \
                    and isinstance(node.target, ast.Name) \
                    and node.target.id == name:
                return f"`{name}` is an accumulating local " \
                       f"(augmented assignment)"
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                for e in ast.walk(node.target):
                    if isinstance(e, ast.Name) and e.id == name:
                        return f"`{name}` iterates a runtime sequence"
        if assigns:
            for v, pos in assigns:
                r = self._elem_bounded(sf, func, v, pos, depth)
                if r:
                    return r
            return None
        # a parameter: bounded iff every exact caller passes bounded
        if isinstance(func, _FUNC_DEFS):
            params = [a.arg for a in (func.args.posonlyargs
                                      + func.args.args)]
            if name in params:
                return self._param_bounded(sf, func, params.index(name),
                                           name, depth)
            # closure variable: resolve in the lexically enclosing def
            outer = enclosing_function(func)
            if outer is not None:
                return self._name_bounded(sf, outer, name, depth + 1)
        # module-level constant
        mod_assigns = [
            node.value for node in sf.tree.body
            if isinstance(node, ast.Assign)
            for t in node.targets
            if isinstance(t, ast.Name) and t.id == name
        ]
        if mod_assigns:
            for v in mod_assigns:
                r = self._bounded(sf, func, v, depth + 1)
                if r:
                    return r
            return None
        return f"`{name}` has no visible bounded binding"

    def _elem_bounded(self, sf: SourceFile, func, value, pos: Optional[int],
                      depth: int) -> Optional[str]:
        """Boundedness of one unpacked element: select ``elts[pos]``
        when the value is a literal tuple, and thread the position
        through a parameter so ``(a, b) = payload`` checks element
        ``pos`` of each caller's literal payload tuple."""
        if pos is not None and isinstance(value, (ast.Tuple, ast.List)) \
                and pos < len(value.elts):
            return self._bounded(sf, func, value.elts[pos], depth + 1)
        if pos is not None and isinstance(value, ast.Name) \
                and isinstance(func, _FUNC_DEFS):
            params = [a.arg for a in (func.args.posonlyargs
                                      + func.args.args)]
            local_tuples = [
                node.value for node in ast.walk(func)
                if isinstance(node, ast.Assign)
                for t in node.targets
                if isinstance(t, ast.Name) and t.id == value.id
            ]
            if local_tuples:
                for v in local_tuples:
                    r = self._elem_bounded(sf, func, v, pos, depth + 1)
                    if r:
                        return r
                return None
            if value.id in params:
                return self._param_bounded(sf, func,
                                           params.index(value.id),
                                           value.id, depth, elem=pos)
        return self._bounded(sf, func, value, depth + 1)

    def _param_bounded(self, sf: SourceFile, func, idx: int, name: str,
                       depth: int,
                       elem: Optional[int] = None) -> Optional[str]:
        qual = self.cg._def_qual.get(id(func))
        if qual is None:
            return f"parameter `{name}` of an unindexed function"
        memo_key = (qual, name if elem is None else f"{name}[{elem}]")
        if memo_key in self._bounded_memo:
            return self._bounded_memo[memo_key]
        if memo_key in self._attr_inprog:
            return None
        self._attr_inprog.add(memo_key)
        try:
            fi = self.cg.funcs.get(qual)
            # methods: caller positional j maps to param j+1 — keyed on
            # the first parameter being self/cls (a nested function
            # keeps its class CONTEXT in the callgraph but receives no
            # implicit receiver)
            shift = _self_shift(func, fi)
            callers = []
            for caller_q, csites in self.cg.calls.items():
                for cs in csites:
                    if cs.kind == "call" and not cs.fuzzy \
                            and qual in cs.targets:
                        callers.append((caller_q, cs.node))
            if not callers:
                res: Optional[str] = \
                    f"parameter `{name}` has no resolvable callers"
            else:
                res = None
                for caller_q, cnode in callers:
                    pos = idx - shift
                    arg_expr = None
                    if 0 <= pos < len(cnode.args):
                        arg_expr = cnode.args[pos]
                    else:
                        for kw in cnode.keywords:
                            if kw.arg == name:
                                arg_expr = kw.value
                    if arg_expr is None:
                        continue            # defaulted: checked below
                    c_fi = self.cg.funcs.get(caller_q)
                    c_sf = c_fi.sf if c_fi is not None else sf
                    c_func = c_fi.node if c_fi is not None else None
                    if elem is None:
                        r = self._bounded(c_sf, c_func, arg_expr,
                                          depth + 1)
                    else:
                        r = self._elem_bounded(c_sf, c_func, arg_expr,
                                               elem, depth + 1)
                    if r:
                        res = f"caller {caller_q.split('::')[-1]} at " \
                              f"{c_sf.rel}:{cnode.lineno} passes " \
                              f"`{name}` from an unbounded value ({r})"
                        break
            self._bounded_memo[memo_key] = res
            return res
        finally:
            self._attr_inprog.discard(memo_key)

    # --------------------------------------------------- rule: recompile
    def _check_recompile(self) -> None:
        out = self._findings["jax-recompile"]
        for sid in sorted(self.sites):
            site = self.sites[sid]
            call = site.node
            # jit(f)(x): a fresh wrapper (and compile-cache entry) per
            # invocation — bind the wrapper once instead
            parent = getattr(call, "parent", None)
            if isinstance(call, ast.Call) and isinstance(parent, ast.Call) \
                    and parent.func is call:
                out.append(site.sf.finding(
                    "jax-recompile", call,
                    f"jit wrapper for `{site.target_name}` is created "
                    f"and invoked in one expression — every execution "
                    f"builds a fresh wrapper and compile-cache entry; "
                    f"bind the jitted function once and reuse it"))
            # jit(...) inside a loop: one wrapper per iteration
            cur = parent
            while cur is not None and not isinstance(cur, _FUNC_DEFS):
                if isinstance(cur, _LOOPS):
                    out.append(site.sf.finding(
                        "jax-recompile", call,
                        f"jit wrapper for `{site.target_name}` is "
                        f"created inside a loop — each iteration "
                        f"compiles from scratch; hoist the jit() out"))
                    break
                cur = getattr(cur, "parent", None)
            if not site.statics:
                continue
            for cs in site.call_sites:
                func = enclosing_function(cs)
                nonhash: List[int] = []
                loose: List[Tuple[int, str]] = []
                for i in sorted(site.statics):
                    if i >= len(cs.args):
                        continue
                    arg = cs.args[i]
                    if isinstance(arg, (ast.List, ast.Dict, ast.Set)) \
                            or (isinstance(arg, ast.Call)
                                and call_name(arg).rsplit(".", 1)[-1]
                                in ("array", "asarray")):
                        site.unbounded.append(
                            (cs, i, "non-hashable static"))
                        nonhash.append(i)
                        continue
                    reason = self._bounded(self._sf_of(cs, site),
                                           func, arg)
                    if reason:
                        site.unbounded.append((cs, i, reason))
                        loose.append((i, reason))
                if nonhash:
                    out.append(self._sf_of(cs, site).finding(
                        "jax-recompile", cs,
                        f"static_argnums position(s) {nonhash} of "
                        f"`{site.target_name}` receive non-hashable "
                        f"values — jit statics must be hashable "
                        f"(TypeError at trace time)"))
                if loose:
                    # one finding per CALL SITE: one reasoned pragma on
                    # the dispatch line covers every loose static there
                    positions = [i for i, _ in loose]
                    out.append(self._sf_of(cs, site).finding(
                        "jax-recompile", cs,
                        f"static_argnums position(s) {positions} of "
                        f"jitted `{site.target_name}` ({sid}) are not "
                        f"provably drawn from a bounded set: "
                        f"{loose[0][1]} — every distinct value compiles "
                        f"a new program; route them through a "
                        f"ShapeSchedule cap / bucket rung, or suppress "
                        f"with the boundedness argument"))

    def _sf_of(self, node, site: JitSite) -> SourceFile:
        # call sites matched by bound key live in the site's own file;
        # decorated-def call sites can live anywhere in the project
        owner = self.cg.owner_of.get(id(node))
        if owner:
            fi = self.cg.funcs.get(owner)
            if fi is not None:
                return fi.sf
        return site.sf

    # --------------------------------------------------- rule: host sync
    def _check_host_sync(self) -> None:
        out = self._findings["jax-host-sync"]
        wrapper_by_call = self._call_to_wrapper_index()
        # which hot functions RETURN device values (callers taint their
        # results), and which parameters are fed device values — one
        # fixpoint over the hot set
        device_returns: Set[str] = set()
        param_taint: Dict[str, Set[str]] = {}
        changed = True
        rounds = 0
        while changed and rounds < 6:
            changed = False
            rounds += 1
            for qual in sorted(self.hot_funcs):
                fi = self.cg.funcs.get(qual)
                if fi is None or fi.node is None:
                    continue
                tainted = self._taint_names(
                    fi, wrapper_by_call, device_returns,
                    param_taint.get(qual, set()))
                # returns a device value?
                for n in ast.walk(fi.node):
                    if isinstance(n, ast.Return) and n.value is not None \
                            and self._expr_tainted(
                                n.value, tainted, wrapper_by_call,
                                device_returns, fi):
                        if qual not in device_returns:
                            device_returns.add(qual)
                            changed = True
                        break
                # propagate into callee parameters
                for cs in self.cg.calls.get(qual, []):
                    if cs.kind != "call" or cs.fuzzy:
                        continue
                    for t in cs.targets:
                        if t not in self.hot_funcs:
                            continue
                        ti = self.cg.funcs.get(t)
                        if ti is None or ti.node is None:
                            continue
                        params = [a.arg for a in
                                  (ti.node.args.posonlyargs
                                   + ti.node.args.args)]
                        shift = _self_shift(ti.node, ti)
                        for j, a in enumerate(cs.node.args):
                            pj = j + shift
                            if pj < len(params) and self._expr_tainted(
                                    a, tainted, wrapper_by_call,
                                    device_returns, fi):
                                cur = param_taint.setdefault(t, set())
                                if params[pj] not in cur:
                                    cur.add(params[pj])
                                    changed = True
        # flag sinks
        for qual in sorted(self.hot_funcs):
            fi = self.cg.funcs.get(qual)
            if fi is None or fi.node is None:
                continue
            if fi.sf.rel.endswith("utils/jaxtrace.py"):
                continue    # fetch() IS the declared sync

            tainted = self._taint_names(
                fi, wrapper_by_call, device_returns,
                param_taint.get(qual, set()))
            for call in ast.walk(fi.node):
                if not isinstance(call, ast.Call):
                    continue
                cn = call_name(call)
                tail = cn.rsplit(".", 1)[-1]
                sink = None
                if cn in _COERCIONS and call.args:
                    sink = call.args[0]
                elif tail in _NP_SINKS and call.args \
                        and cn.partition(".")[0] in ("np", "numpy"):
                    sink = call.args[0]
                elif isinstance(call.func, ast.Attribute) \
                        and call.func.attr in _ITEM_SINKS:
                    sink = call.func.value
                elif cn == "print":
                    for a in call.args:
                        if self._expr_tainted(a, tainted, wrapper_by_call,
                                              device_returns, fi):
                            sink = a
                            break
                if sink is None:
                    continue
                if not self._expr_tainted(sink, tainted, wrapper_by_call,
                                          device_returns, fi):
                    continue
                if self._inside_fetch(call):
                    continue
                what = dotted(sink) or type(sink).__name__
                out.append(fi.sf.finding(
                    "jax-host-sync", call,
                    f"device value `{what}` is coerced to host by "
                    f"`{cn or call.func.attr}` inside the hot "
                    f"step/dispatch path "
                    f"({qual.split('::', 1)[1]}) — an implicit blocking "
                    f"device->host sync every iteration; batch the "
                    f"fetch, or declare the sync with "
                    f"utils.jaxtrace.fetch(x) so the runtime tracer "
                    f"audits it"))

    def _call_to_wrapper_index(self) -> Dict[int, JitSite]:
        idx: Dict[int, JitSite] = {}
        for site in self.sites.values():
            for c in site.call_sites:
                idx[id(c)] = site
        return idx

    def _taint_names(self, fi, wrapper_by_call, device_returns,
                     pre_tainted: Set[str]) -> Set[str]:
        """Names in ``fi`` holding device values: results of jit
        wrapper calls / device-returning hot helpers, via (tuple)
        assignment, plus device-tainted parameters."""
        tainted = set(pre_tainted)
        for _ in range(3):                   # tiny local fixpoint
            grew = False
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Assign):
                    continue
                if not self._expr_tainted(node.value, tainted,
                                          wrapper_by_call,
                                          device_returns, fi):
                    continue
                for t in node.targets:
                    for e in ast.walk(t):
                        if isinstance(e, ast.Name) \
                                and e.id not in tainted:
                            tainted.add(e.id)
                            grew = True
            if not grew:
                break
        return tainted

    def _expr_tainted(self, expr, tainted: Set[str], wrapper_by_call,
                      device_returns, fi) -> bool:
        if isinstance(expr, ast.Name):
            return expr.id in tainted
        if isinstance(expr, ast.Call):
            if id(expr) in wrapper_by_call:
                return True
            cn = call_name(expr)
            tail = cn.rsplit(".", 1)[-1]
            if _is_fetch_call(cn):
                return False                 # declared sync: host after
            if tail in _NP_SINKS | _COERCIONS | _ITEM_SINKS:
                return False                 # already host
            # calls into device-returning hot helpers
            owner = self.cg.owner_of.get(id(expr))
            if owner is not None:
                cs = self.cg.by_node.get(id(expr))
                if cs is not None and cs.kind == "call" and not cs.fuzzy:
                    if any(t in device_returns for t in cs.targets):
                        return True
            return False
        if isinstance(expr, (ast.Tuple, ast.List)):
            return any(self._expr_tainted(e, tainted, wrapper_by_call,
                                          device_returns, fi)
                       for e in expr.elts)
        if isinstance(expr, ast.Subscript):
            return self._expr_tainted(expr.value, tainted,
                                      wrapper_by_call, device_returns, fi)
        if isinstance(expr, ast.Starred):
            return self._expr_tainted(expr.value, tainted,
                                      wrapper_by_call, device_returns, fi)
        if isinstance(expr, ast.BinOp):
            return self._expr_tainted(expr.left, tainted, wrapper_by_call,
                                      device_returns, fi) \
                or self._expr_tainted(expr.right, tainted,
                                      wrapper_by_call, device_returns, fi)
        return False

    @staticmethod
    def _inside_fetch(node) -> bool:
        cur = getattr(node, "parent", None)
        while cur is not None and not isinstance(cur, ast.stmt):
            if isinstance(cur, ast.Call) and _is_fetch_call(call_name(cur)):
                return True
            cur = getattr(cur, "parent", None)
        return False

    # ------------------------------------------------- rule: donate flow
    def _check_donate_flow(self) -> None:
        out = self._findings["jax-donate-flow"]
        for sid in sorted(self.sites):
            site = self.sites[sid]
            if not site.donates:
                continue
            overlap = set(site.donates) & set(site.statics)
            if overlap:
                out.append(site.sf.finding(
                    "jax-donate-flow", site.node,
                    f"donate_argnums {sorted(overlap)} of "
                    f"`{site.target_name}` are also static_argnums — "
                    f"statics are compile-time values, not buffers; "
                    f"nothing can be donated there"))
            if isinstance(site.target_node, _FUNC_DEFS):
                npos = len(site.target_node.args.posonlyargs) \
                    + len(site.target_node.args.args)
                past = [i for i in site.donates if i >= npos]
                if past:
                    out.append(site.sf.finding(
                        "jax-donate-flow", site.node,
                        f"donate_argnums {past} of `{site.target_name}` "
                        f"point past its {npos} positional parameters — "
                        f"the donation silently never happens"))
            for cs in site.call_sites:
                names = {}
                for j, a in enumerate(cs.args):
                    if isinstance(a, ast.Name):
                        names.setdefault(a.id, []).append(j)
                for nm, positions in names.items():
                    don = [j for j in positions if j in site.donates]
                    other = [j for j in positions
                             if j not in site.donates]
                    if don and other:
                        out.append(self._sf_of(cs, site).finding(
                            "jax-donate-flow", cs,
                            f"`{nm}` is passed to `{site.target_name}` "
                            f"at donated position {don[0]} AND "
                            f"non-donated position {other[0]} — the "
                            f"non-donated alias reads a deleted buffer"))
                self._cross_edge_donate(site, cs, out)

    def _cross_edge_donate(self, site: JitSite, cs: ast.Call,
                           out: List[Finding]) -> None:
        """The donated argument is the enclosing function's parameter:
        exact callers must not read the buffer they passed after the
        call returns (the interprocedural half of jax-donate)."""
        func = enclosing_function(cs)
        if not isinstance(func, _FUNC_DEFS):
            return
        qual = self.cg._def_qual.get(id(func))
        if qual is None:
            return
        fi = self.cg.funcs.get(qual)
        params = [a.arg for a in (func.args.posonlyargs + func.args.args)]
        shift = _self_shift(func, fi)
        stmt = statement_of(cs)
        # x = f(x) rebinding inside the wrapper's own function makes the
        # flow safe for the LOCAL name; the caller's buffer is donated
        # regardless — but only a param that is NOT rebound into the
        # return value propagates the hazard conservatively: we flag
        # only when the callee neither rebinds nor returns the result
        for i in sorted(site.donates):
            if i >= len(cs.args) or not isinstance(cs.args[i], ast.Name):
                continue
            pname = cs.args[i].id
            if pname not in params:
                continue
            pidx = params.index(pname)
            for caller_q, csites in self.cg.calls.items():
                for outer in csites:
                    if outer.kind != "call" or outer.fuzzy \
                            or qual not in outer.targets:
                        continue
                    pos = pidx - shift
                    if not (0 <= pos < len(outer.node.args)):
                        continue
                    passed = outer.node.args[pos]
                    if not isinstance(passed, ast.Name):
                        continue
                    c_fi = self.cg.funcs.get(caller_q)
                    if c_fi is None or c_fi.node is None:
                        continue
                    ostmt = statement_of(outer.node)
                    rebound: Set[str] = set()
                    if isinstance(ostmt, ast.Assign):
                        for t in ostmt.targets:
                            for n in ast.walk(t):
                                if isinstance(n, ast.Name):
                                    rebound.add(n.id)
                    if passed.id in rebound:
                        continue
                    for n in ast.walk(c_fi.node):
                        if isinstance(n, ast.Name) \
                                and n.id == passed.id \
                                and isinstance(n.ctx, ast.Load) \
                                and n.lineno > ostmt.end_lineno:
                            out.append(c_fi.sf.finding(
                                "jax-donate-flow", n,
                                f"`{passed.id}` is read here after "
                                f"being passed to "
                                f"{qual.split('::', 1)[1]} (line "
                                f"{ostmt.lineno}), which donates it to "
                                f"jitted `{site.target_name}` "
                                f"(donate_argnums={i}) — the buffer is "
                                f"deleted inside the callee; rebind or "
                                f"stop reading it"))
                            break

    # ------------------------------------------------------------ views
    def known_warm(self) -> Set[str]:
        """Jit sites whose every static at every call site is bounded —
        or whose unbounded call sites all carry a reasoned
        jax-recompile suppression. These are the sites the tier-1
        JAXTRACE gate accepts in the steady state."""
        out = set()
        by_rel = {sf.rel: sf for sf in self.project.files}
        for sid, site in self.sites.items():
            ok = True
            for cs, _i, _r in site.unbounded:
                sf = self._sf_of(cs, site)
                sf = by_rel.get(sf.rel, sf)
                if "jax-recompile" not in sf.suppressions.get(
                        cs.lineno, set()):
                    ok = False
                    break
            if ok:
                out.add(sid)
        return out

    def declared_fetches(self) -> Set[str]:
        return set(self.fetch_sites)

    def to_json(self) -> dict:
        return {
            "sites": {
                sid: {
                    "target": site.target_name,
                    "bound": site.bound,
                    "static_argnums": list(site.statics),
                    "donate_argnums": list(site.donates),
                    "call_sites": sorted(
                        {f"{self._sf_of(c, site).rel}:{c.lineno}"
                         for c in site.call_sites}),
                    "warm_bounded": sid in self.known_warm(),
                    "unbounded": [
                        {"call": f"{self._sf_of(c, site).rel}:{c.lineno}",
                         "static": i, "reason": r}
                        for c, i, r in site.unbounded],
                }
                for sid, site in sorted(self.sites.items())
            },
            "fetch_sites": sorted(self.fetch_sites),
            "hot_roots": sorted(self.hot_roots),
        }


def get_jax_model(project: Project) -> JaxModel:
    m = getattr(project, "_jax_model", None)
    if m is None or m.project is not project:
        m = JaxModel(project)
        project._jax_model = m  # type: ignore[attr-defined]
    return m


# ---------------------------------------------------------------------------
# rule registrations


@rule("jax-recompile",
      "jit statics must come from a bounded set (the compile-key model)",
      cross=True)
def check_jax_recompile(project: Project) -> List[Finding]:
    return list(get_jax_model(project)._findings["jax-recompile"])


@rule("jax-host-sync",
      "no implicit device->host coercions on the hot dispatch path",
      cross=True)
def check_jax_host_sync(project: Project) -> List[Finding]:
    return list(get_jax_model(project)._findings["jax-host-sync"])


@rule("jax-donate-flow",
      "donation declarations must alias, and donated buffers must not "
      "be read by callers", cross=True)
def check_jax_donate_flow(project: Project) -> List[Finding]:
    return list(get_jax_model(project)._findings["jax-donate-flow"])


# --------------------------------------------------------------- local rule


_F64 = ("float64",)


def _mentions_float64(node) -> bool:
    if isinstance(node, ast.Attribute) and node.attr in _F64:
        return True
    if isinstance(node, ast.Constant) and node.value == "float64":
        return True
    return False


@rule("jax-dtype64",
      "no float64 drift into the fp32 device pipeline; no int32 "
      "accumulators on overflow paths")
def check_jax_dtype64(sf: SourceFile) -> List[Finding]:
    from .localrules import _jitted_functions
    out: List[Finding] = []
    # float64 inside jit targets: one float64 intermediate promotes the
    # whole fp32 computation on device
    for fn in _jitted_functions(sf):
        for n in ast.walk(ast.Module(body=fn.body, type_ignores=[])):
            if _mentions_float64(n):
                out.append(sf.finding(
                    "jax-dtype64", n,
                    f"float64 inside jitted `{fn.name}` promotes the "
                    f"fp32 pipeline (or fails under the default x64 "
                    f"disable) — keep device math in float32, or do "
                    f"the float64 reduction on host"))
    # dtype=float64 on jnp device-array creation anywhere
    for call in sf.call_nodes():
        cn = call_name(call)
        if not cn.startswith("jnp."):
            continue
        for kw in call.keywords:
            if kw.arg == "dtype" and _mentions_float64(kw.value):
                out.append(sf.finding(
                    "jax-dtype64", call,
                    f"`{cn}(dtype=float64)` creates a float64 device "
                    f"array — the fp32 pipeline promotes on first "
                    f"contact; use float32 (host-side float64 staging "
                    f"is fine, convert before device_put)"))
    # int32 accumulators in loops: row counters overflow past 2^31
    int32_names: Dict[str, Set[str]] = {}
    for fn in [n for n in sf.walk() if isinstance(n, _FUNC_DEFS)]:
        names: Set[str] = set()
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            t = node.targets[0]
            if not isinstance(t, ast.Name):
                continue
            v = node.value
            is32 = False
            if isinstance(v, ast.Call):
                vn = call_name(v)
                if vn.rsplit(".", 1)[-1] == "int32":
                    is32 = True
                for kw in v.keywords:
                    if kw.arg == "dtype" and (
                            (isinstance(kw.value, ast.Attribute)
                             and kw.value.attr == "int32")
                            or (isinstance(kw.value, ast.Constant)
                                and kw.value.value == "int32")):
                        is32 = True
            if is32:
                names.add(t.id)
        if names:
            int32_names[fn.name] = names
            for node in ast.walk(fn):
                if isinstance(node, ast.AugAssign) \
                        and isinstance(node.target, ast.Name) \
                        and node.target.id in names:
                    cur = getattr(node, "parent", None)
                    in_loop = False
                    while cur is not None and cur is not fn:
                        if isinstance(cur, _LOOPS):
                            in_loop = True
                            break
                        cur = getattr(cur, "parent", None)
                    if in_loop:
                        out.append(sf.finding(
                            "jax-dtype64", node,
                            f"`{node.target.id}` is an int32-created "
                            f"accumulator incremented in a loop — row "
                            f"counters overflow past 2^31 on "
                            f"production-size streams; count in int64"))
    return out
