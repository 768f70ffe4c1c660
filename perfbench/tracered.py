"""From a profiler trace to metrics.

``load_events`` turns the profiler's ``.xplane.pb`` into plain rows
``(plane, line, name, start_ns, dur_ns)``; everything else here works on
such rows, so it is checked on a small recorded list of them
(``tests/perfbench/data/``). Device planes are ``/device:TPU:<n>``; their
line ``XLA Ops`` holds one event an operation, ``XLA Modules`` one event a
program run. Host planes hold the threads' TraceMe events; two of them,
``perfbench_window_open`` and ``perfbench_window_close``, are the
harness's own and give the window's span on the trace's clock. Device
events are clipped to it, so that nothing outside ``window_s`` counts as
busy.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
MARKS = ("perfbench_window_open", "perfbench_window_close")
# The trace gives an op its HLO text, "%all-reduce.3 = f32[8]{0}
# all-reduce(...)". XLA names an instruction after what made it, so a
# ``psum`` inside a ``shard_map`` is "%psum_invariant.7 = ... all-reduce(":
# a collective is found by its leading name or by the opcode behind the
# "=", the first word there that a "(" follows.
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|all-to-all|collective-permute|"
    r"reduce-scatter|collective-broadcast|send|recv)\b")
OPCODE = re.compile(r"=\s.*?\s([a-z][a-z0-9\-]*)\(")


def is_collective(op: str) -> bool:
    if COLLECTIVE.match(op):
        return True
    opcode = OPCODE.search(op)
    return bool(opcode and COLLECTIVE.match(opcode.group(1)))


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_events(path: str, max_host_events: int = 400_000) -> list:
    """Rows (plane, line, name, start_ns, dur_ns) of the device planes'
    op and module lines, and of the host planes' longer events."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    rows = []
    host = []
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev:
                if line.name not in (OP_LINE, MODULE_LINE):
                    continue
                for e in line.events:
                    rows.append((plane.name, line.name, e.name,
                                 int(e.start_ns), int(e.duration_ns)))
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name in MARKS:
                        rows.append((plane.name, line.name, e.name,
                                     int(e.start_ns), int(e.duration_ns)))
                    elif e.duration_ns >= 100_000:   # 0.1 ms and longer
                        host.append((plane.name, line.name, e.name,
                                     int(e.start_ns), int(e.duration_ns)))
    host.sort(key=lambda r: -r[4])
    return rows + host[:max_host_events]


def clip(events: list, lo: float, hi: float) -> list:
    """(name, start, dur) events cut to the span [lo, hi]; those wholly
    outside go."""
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union_seconds(starts, durs) -> tuple:
    """(seconds covered by the union of the intervals, merged starts,
    merged ends), all in the events' own clock."""
    if len(starts) == 0:
        return 0.0, np.zeros(0), np.zeros(0)
    s = np.asarray(starts, np.float64)
    e = s + np.asarray(durs, np.float64)
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    run_end = np.maximum.accumulate(e)
    first = np.ones(len(s), bool)
    first[1:] = s[1:] > run_end[:-1]
    ms = s[first]
    idx = np.flatnonzero(first)
    me = run_end[np.concatenate((idx[1:] - 1, [len(s) - 1]))]
    return float((me - ms).sum()) * 1e-9, ms, me


def reduce(rows: list, window_s: float) -> dict:
    """Busy and idle time, collectives, the operations that took most
    time and the longest idle gaps, from event rows over a window of
    ``window_s`` seconds (the host's clock around the trace)."""
    devices = {}
    host = []
    span = {}
    for plane, line, name, start, dur in rows:
        if DEVICE_PLANE.match(plane):
            devices.setdefault(plane, {}).setdefault(line, []).append(
                (name, start, dur))
        elif name in MARKS:
            span[name] = start
        else:
            host.append((name, start, dur))
    if not devices:
        return {"devices": 0, "busy_s": 0.0, "window_s": window_s}
    clipped = len(span) == 2 and span[MARKS[1]] > span[MARKS[0]]

    busy, coll, ops_total, modules = [], [], {}, {}
    unclipped = []
    gaps_src = None
    for plane in sorted(devices):
        ops = devices[plane].get(OP_LINE, [])
        unclipped.append(union_seconds([o[1] for o in ops],
                                       [o[2] for o in ops])[0])
        if clipped:
            ops = clip(ops, span[MARKS[0]], span[MARKS[1]])
        st = [o[1] for o in ops]
        du = [o[2] for o in ops]
        b, ms, me = union_seconds(st, du)
        busy.append(b)
        c = [(o[1], o[2]) for o in ops if is_collective(o[0])]
        coll.append(union_seconds([x[0] for x in c],
                                  [x[1] for x in c])[0])
        if gaps_src is None or b > gaps_src[0]:
            gaps_src = (b, ms, me)
        for name, _, dur in ops:
            ops_total[name] = ops_total.get(name, 0.0) + dur * 1e-9
        for name, _, _ in devices[plane].get(MODULE_LINE, []):
            modules[name] = modules.get(name, 0) + 1
    n = len(devices)
    for name in ops_total:
        ops_total[name] /= n
    for name in modules:
        modules[name] = modules[name] // n or 1

    out = {
        "devices": n,
        "busy_s": float(np.mean(busy)),
        "busy_s_fullest": float(np.max(busy)),
        "busy_s_unclipped": float(np.mean(unclipped)),
        "clipped": bool(clipped),
        "collective_s_fullest": float(np.max(coll)),
        "window_s": float(window_s),
        "modules": modules,
        "device_ops": sorted(([k, v] for k, v in ops_total.items()),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": _gaps(gaps_src[1], gaps_src[2], host)[:10],
    }
    return out


def _gaps(ms, me, host) -> list:
    """The fullest device's idle gaps, summed by the host event that
    covers most of each: [[name, seconds], ...], longest first."""
    if len(ms) < 2:
        return []
    g0, g1 = me[:-1], ms[1:]
    keep = (g1 - g0) >= 50_000          # gaps of 0.05 ms and more
    g0, g1 = g0[keep], g1[keep]
    if len(g0) == 0:
        return []
    label = np.full(len(g0), -1, np.int64)
    best = np.zeros(len(g0))
    names = []
    # the longest few thousand host events decide the labels
    for i, (name, start, dur) in enumerate(host[:5000]):
        names.append(name)
        lo = np.searchsorted(g1, start, side="right")
        hi = np.searchsorted(g0, start + dur, side="left")
        if hi <= lo:
            continue
        ov = (np.minimum(g1[lo:hi], start + dur)
              - np.maximum(g0[lo:hi], start))
        sel = ov > best[lo:hi]
        best[lo:hi][sel] = ov[sel]
        label[lo:hi][sel] = i
    total = {}
    for lab, a, b in zip(label, g0, g1):
        key = names[lab] if lab >= 0 else "no host event of 0.1 ms"
        total[key] = total.get(key, 0.0) + (b - a) * 1e-9
    return sorted(([k, v] for k, v in total.items()),
                  key=lambda kv: -kv[1])
