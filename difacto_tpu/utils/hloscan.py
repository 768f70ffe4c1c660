"""Compiled-HLO collective/memory scan: the runtime half of the
sharding-flow analyzer (analysis/shardflow.py is the static half),
mirroring how jaxtrace.py complements jaxflow, locktrace the lock-order
model and shared.py the race model.

The static pass proves the *source* threads the fs layout (pins,
no axis-breakers, no replication) — but GSPMD partitioning happens at
compile time, and the compiled HLO is the only artifact that cannot
lie: if output-layout inference decided to re-gather the key-range-
sharded table, there is an ``all-gather`` (or ``all-to-all``) with the
table's full row count in its shape sitting in ``compiled.as_text()``,
and ``compiled.memory_analysis()`` shows the blown temp arena.

With ``DIFACTO_HLOSCAN=1`` every jit program created through
``utils/jaxtrace.jit``/``pjit`` (the tracer is implied on — jaxtrace
``enabled()`` honors this knob too) is lowered and compiled ONCE per
new argument signature BEFORE the real call (lowering only reads
avals, so donation is unaffected), and the scan records, per jit-site
identity (the same ``relpath:lineno`` jaxtrace and jaxflow use):

- every collective in the optimized HLO (kind + the shape dims on its
  line), with ``all-gather``/``all-to-all`` carrying the table's row
  count (``DIFACTO_HLOSCAN_ROWS``) classified **table-axis** — the
  sharded capacity axis moved whole across the mesh;
- ``memory_analysis()`` byte counts, checked against the per-program
  peak-temp budget ``DIFACTO_HLOSCAN_BUDGET`` (bytes; 0 = no budget).

``DIFACTO_HLOSCAN_OUT=<path>`` dumps the scan as JSON at process exit
(same contract as DIFACTO_JAXTRACE_OUT). ``tools/hlomap.py`` merges
the dump with the static shardflow model — ``--check`` fails CI on any
table-axis collective, budget breach, or dynamic site outside the
static model; the tier-1 gate (tests/test_hloscan.py) drives the fs=4
train step and serve executor through it on the CPU virtual mesh.

Scan mode compiles each new signature twice (the scan's
``lower().compile()`` plus the real dispatch) — a diagnostic-mode cost,
never paid when disabled (the default: everything here short-circuits
on one env read).
"""

from __future__ import annotations

import atexit
import json
import os
import re
import sys
import threading
from pathlib import Path
from typing import Dict, List, Optional

_mu = threading.Lock()
_programs: Dict[str, dict] = {}     # site -> scan record
_seen: Dict[str, set] = {}          # site -> arg signatures scanned

# one optimized-HLO line, e.g.
#   %all-gather = f32[512,4]{1,0} all-gather(f32[128,4]{1,0} %p), ...
_COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-to-all|all-reduce|reduce-scatter|"
    r"collective-permute)[\w.-]*\(")
_SHAPE_RE = re.compile(r"\[([0-9][0-9,]*)\]")

# only these move an axis whole across the mesh; all-reduce /
# reduce-scatter combine VALUES and are expected (gradient combines)
_TABLE_AXIS_KINDS = ("all-gather", "all-to-all")


def enabled() -> bool:
    return os.environ.get("DIFACTO_HLOSCAN", "") not in ("", "0")


def table_rows() -> int:
    """The full (unsharded) table row count whose appearance in an
    all-gather/all-to-all shape marks a table-axis collective; 0 (the
    default) disables the classification."""
    try:
        return int(os.environ.get("DIFACTO_HLOSCAN_ROWS", "0"))
    except ValueError:
        return 0


def temp_budget() -> int:
    """Per-program peak temp-arena budget in bytes; 0 = no budget."""
    try:
        return int(os.environ.get("DIFACTO_HLOSCAN_BUDGET", "0"))
    except ValueError:
        return 0


def scan_text(text: str, rows: int = 0) -> List[dict]:
    """All collectives in an (optimized) HLO dump: ``{kind, dims,
    table_axis, line}`` per occurrence. ``table_axis`` is True for an
    all-gather/all-to-all whose line carries a shape dimension equal to
    ``rows`` — the sharded capacity axis re-materialized whole."""
    out = []
    for line in text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        dims = sorted({int(d) for g in _SHAPE_RE.findall(line)
                       for d in g.split(",") if d})
        out.append({
            "kind": kind,
            "dims": dims,
            "table_axis": bool(rows) and kind in _TABLE_AXIS_KINDS
            and rows in dims,
            "line": line.strip()[:200],
        })
    return out


def _memory(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:                           # pragma: no cover
        # some backends ship executables without memory stats; the
        # collective scan must still run, so note it and move on
        print(f"hloscan: memory_analysis unavailable: {e}",
              file=sys.stderr)
        return {}
    out = {}
    for k in ("temp_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def scan_compiled(compiled, rows: Optional[int] = None,
                  budget: Optional[int] = None, label: str = "") -> dict:
    """Scan ONE compiled executable (no registry side effect):
    collectives + memory_analysis + the table-axis/budget verdicts.
    ``rows``/``budget`` default to the env knobs — callers that know
    their own table geometry (parallel/capacity.py legs) pass them."""
    rows = table_rows() if rows is None else rows
    budget = temp_budget() if budget is None else budget
    colls = scan_text(compiled.as_text(), rows)
    mem = _memory(compiled)
    temp = mem.get("temp_size_in_bytes", 0)
    return {
        "label": label,
        "collectives": colls,
        "table_collectives": sum(1 for c in colls if c["table_axis"]),
        "memory": mem,
        "peak_temp_bytes": temp,
        "over_budget": bool(budget) and temp > budget,
        "signatures": 1,
    }


def record(site: str, compiled, label: str = "",
           rows: Optional[int] = None,
           budget: Optional[int] = None) -> dict:
    """Scan one compiled executable under the jit-site identity
    ``site`` and remember the worst view per site (collectives union,
    max temp bytes across signatures)."""
    rec = scan_compiled(compiled, rows=rows, budget=budget, label=label)
    colls = rec["collectives"]
    temp = rec["peak_temp_bytes"]
    with _mu:
        prev = _programs.get(site)
        if prev is not None:
            rec["collectives"] = prev["collectives"] + colls
            rec["table_collectives"] += prev["table_collectives"]
            rec["peak_temp_bytes"] = max(temp, prev["peak_temp_bytes"])
            rec["over_budget"] = rec["over_budget"] or prev["over_budget"]
            rec["signatures"] = prev["signatures"] + 1
            if not rec["label"]:
                rec["label"] = prev["label"]
        _programs[site] = rec
    return rec


def scan_fn(site: str, fn, args: tuple, kwargs: Optional[dict] = None,
            label: str = "", rows: Optional[int] = None,
            budget: Optional[int] = None) -> Optional[dict]:
    """Lower+compile ``fn`` on ``args`` and :func:`record` it — the
    explicit entry capacity.py and the tests use. Returns the record,
    or None when ``fn`` cannot lower (not a jit wrapper)."""
    if not hasattr(fn, "lower"):
        return None
    compiled = fn.lower(*args, **(kwargs or {})).compile()
    return record(site, compiled,
                  label or getattr(fn, "__name__", ""),
                  rows=rows, budget=budget)


def _sig(args: tuple, kwargs: dict) -> tuple:
    def leaf(a):
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is not None and dtype is not None:
            return ("a", tuple(shape), str(dtype))
        if isinstance(a, (tuple, list)):
            return ("t", tuple(leaf(x) for x in a))
        return ("o", type(a).__name__)
    return tuple(leaf(a) for a in args) + tuple(
        (k, leaf(kwargs[k])) for k in sorted(kwargs))


def maybe_scan(site: str, fn, args: tuple, kwargs: dict) -> None:
    """The jaxtrace ``_TracedJit.__call__`` pre-call hook: scan once
    per (site, argument signature), and never let a scan failure break
    the run it is observing."""
    if not enabled():
        return
    try:
        sig = _sig(args, kwargs)
        with _mu:
            seen = _seen.setdefault(site, set())
            if sig in seen:
                return
            seen.add(sig)
        scan_fn(site, fn, args, kwargs)
    except Exception as e:                           # pragma: no cover
        print(f"hloscan: scan of {site} failed: {e}", file=sys.stderr)


# ----------------------------------------------------------------- data


def programs() -> Dict[str, dict]:
    """Snapshot: jit site -> scan record."""
    with _mu:
        return {s: dict(rec) for s, rec in _programs.items()}


def violations(progs: Optional[Dict[str, dict]] = None) -> List[dict]:
    """Gate view: one entry per table-axis collective or budget breach
    in ``progs`` (default: the live snapshot)."""
    progs = programs() if progs is None else progs
    out = []
    for site, rec in sorted(progs.items()):
        for c in rec.get("collectives", []):
            if c.get("table_axis"):
                out.append({"site": site, "kind": "table-collective",
                            "detail": f"{c['kind']} {c['dims']}"})
        if rec.get("over_budget"):
            out.append({"site": site, "kind": "temp-budget",
                        "detail": f"peak_temp_bytes="
                                  f"{rec.get('peak_temp_bytes')}"})
    return out


def reset() -> None:
    with _mu:
        _programs.clear()
        _seen.clear()


def dump(path) -> str:
    """Write the scan as JSON (stamped with the knobs that shaped it);
    returns the path."""
    payload = {
        "version": 1,
        "rows": table_rows(),
        "budget": temp_budget(),
        "programs": dict(sorted(programs().items())),
    }
    p = Path(path)
    if p.parent and str(p.parent) not in (".", ""):
        p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(payload, indent=1) + "\n", encoding="utf-8")
    return str(p)


def load(path) -> dict:
    """Read a dump() back: {'rows', 'budget', 'programs'}."""
    data = json.loads(Path(path).read_text(encoding="utf-8"))
    if data.get("version") != 1:
        raise ValueError(f"hloscan dump {path}: unsupported version "
                         f"{data.get('version')!r}")
    return {"rows": int(data.get("rows", 0)),
            "budget": int(data.get("budget", 0)),
            "programs": dict(data.get("programs", {}))}


def _atexit_dump() -> None:  # pragma: no cover - process teardown
    out = os.environ.get("DIFACTO_HLOSCAN_OUT", "")
    if out and enabled():
        try:
            dump(out)
        except OSError as e:
            print(f"hloscan: dump to {out} failed: {e}", file=sys.stderr)


atexit.register(_atexit_dump)
