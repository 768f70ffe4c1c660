"""Device time by leg, host time by program span, device idle by the
program span that covers it — from the traced run's ``.xplane.pb``.

The program names the inside of its step with ``jax.named_scope`` legs
(``unpack gather forward backward update scatter evaluate``) and its host
boundaries with spans that land in the live profiler session
(``dispatch``, ``fetch_wait``, ``epoch_turn`` and its children, ...), so
device operations and program spans sit in one file. This module reads that file once more, beside ``tracered.py`` (which knows
neither legs nor spans and may not be edited by the PR that added this):

- an ``XLA Ops`` event's leg is the last leg name in the scope path of
  its ``tf_op`` (the HLO ``op_name``, e.g.
  ``jit(packed_panel_train_chunked2)/update/scatter/scatter:``), the
  final component being the primitive's own name. XLA fuses across
  scopes: a fusion carries the path of its root. ``tf_op`` is a stat of
  the event's *metadata*, which ``jax.profiler.ProfileData`` does not
  hand out, so the metadata tables alone are parsed from the file with a
  five-message schema (``google.protobuf``, no generated module);
- an event that overlaps another (a ``call`` or ``while`` around its
  body) gives its time to the one that started last, so legs sum to the
  busy time;
- a device-idle stretch inside the window goes to the program span that
  covers it and started last (the innermost), else to ``unattributed``;
- the two clocks are not quite one: on the v5e the device planes'
  timestamps run about 1.5 ms EARLY against the host planes' (a step's
  first operation shows before the ``dispatch`` span that enqueued it
  opens), which matters where an idle stretch is 3 ms long. The offset
  is read from the trace itself: the runtime leaves a host event when
  it enqueues a program run (``DoEnqueueProgram``) and one when the run
  has completed (``CompleteCallbacks``), both with the ``run_id`` that
  the run's ``XLA Modules`` event carries, so a run cannot start before
  the first nor end after the second. The largest lower and the
  smallest upper bound over all runs lie 0.2 ms apart; device events
  are shifted by their midpoint (by nothing where either is missing).

Everything is clipped to the harness's two marks. On rows (``reduce``)
the arithmetic is checked against a recorded list in
``tests/perfbench/data/``. Where the file has no TPU plane, or the
program has no legs or spans (the parent of the PR that added them),
the readers find nothing and return ``None``.
"""

from __future__ import annotations

import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
ENQUEUED, COMPLETED = "DoEnqueueProgram", "CompleteCallbacks"
MARKS = ("perfbench_window_open", "perfbench_window_close")

# the program's names (difacto_tpu/obs/names.py; a test pins the two
# lists to each other)
STEP_LEGS = ("gather", "forward", "backward", "update", "scatter")
LEGS = ("unpack",) + STEP_LEGS + ("evaluate",)
OTHER = "other"
TURN = "epoch_turn"
TURN_CHILDREN = ("epoch.merge", "epoch.eval_scalars", "epoch.evict_check",
                 "epoch.callbacks", "replay.iter_parts")
SPANS = ("epoch", "dispatch", "fetch_wait", "merge.stack", "transfer",
         TURN, *TURN_CHILDREN, "consumer.dispatch", "producer.parse",
         "producer.pack", "producer.ring_wait", "compile.pair_exec")
UNATTRIBUTED = "unattributed"


# ------------------------------------------------------------ the file
def find_run_trace(root: str):
    """The live run's ``.xplane.pb``: ``run.py`` hands readers no path,
    and removes ``<root>/.perfbench_run/run_*`` only after they ran."""
    found = glob.glob(os.path.join(
        root, ".perfbench_run", "run_*", "trace", "plugins", "profile",
        "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def _schema():
    """XSpace, cut to what maps an event's name to its metadata's stats
    (tsl/profiler/protobuf/xplane.proto; unknown fields are skipped)."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    T = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="perfbench_xplane_cut.proto", package="perfbench_xplane",
        syntax="proto3")

    def msg(name, *fields):
        m = f.message_type.add(name=name)
        for fname, num, ftype, tname, rep in fields:
            m.field.add(
                name=fname, number=num, type=ftype,
                type_name=".perfbench_xplane." + tname if tname else None,
                label=T.LABEL_REPEATED if rep else T.LABEL_OPTIONAL)

    msg("XStat", ("metadata_id", 1, T.TYPE_INT64, "", 0),
        ("str_value", 5, T.TYPE_STRING, "", 0),
        ("ref_value", 7, T.TYPE_UINT64, "", 0))
    msg("XEventMetadata", ("id", 1, T.TYPE_INT64, "", 0),
        ("name", 2, T.TYPE_STRING, "", 0),
        ("stats", 5, T.TYPE_MESSAGE, "XStat", 1))
    msg("XStatMetadata", ("id", 1, T.TYPE_INT64, "", 0),
        ("name", 2, T.TYPE_STRING, "", 0))
    # a map field on the wire: repeated {key = 1, value = 2}
    msg("EventEntry", ("key", 1, T.TYPE_INT64, "", 0),
        ("value", 2, T.TYPE_MESSAGE, "XEventMetadata", 0))
    msg("StatEntry", ("key", 1, T.TYPE_INT64, "", 0),
        ("value", 2, T.TYPE_MESSAGE, "XStatMetadata", 0))
    msg("XPlane", ("name", 2, T.TYPE_STRING, "", 0),
        ("event_metadata", 4, T.TYPE_MESSAGE, "EventEntry", 1),
        ("stat_metadata", 5, T.TYPE_MESSAGE, "StatEntry", 1))
    msg("XSpace", ("planes", 1, T.TYPE_MESSAGE, "XPlane", 1))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("perfbench_xplane.XSpace"))


def event_scopes(raw: bytes) -> dict:
    """{device plane: {event name: tf_op}} from the file's metadata
    tables; {} where ``google.protobuf`` is missing."""
    try:
        space = _schema()()
    except ImportError:
        return {}
    space.ParseFromString(raw)
    out = {}
    for plane in space.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        stat_name = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op = {k for k, n in stat_name.items() if n == "tf_op"}
        scopes = out.setdefault(plane.name, {})
        for e in plane.event_metadata:
            for s in e.value.stats:
                if s.metadata_id in tf_op:
                    scopes[e.value.name] = (
                        s.str_value or stat_name.get(s.ref_value, ""))
    return out


def _run_id(event) -> str:
    for key, value in event.stats:
        if key == "run_id":
            return f"run_id={value}"
    return ""


def load_rows(path: str) -> list:
    """Rows (plane, line, name, start_ns, dur_ns, scope): the device
    planes' operations with their ``tf_op`` and program runs with their
    ``run_id=<n>``, the two marks, the host events that carry a program
    span's name (scope ""), and the runtime's enqueue and completion
    events (``run_id=<n>``)."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    scopes = event_scopes(raw)
    data = ProfileData.from_serialized_xspace(raw)
    keep = set(SPANS) | set(MARKS)
    rows = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            of = scopes.get(plane.name, {})
            for line in plane.lines:
                if line.name not in (OP_LINE, MODULE_LINE):
                    continue
                ops = line.name == OP_LINE
                for e in line.events:
                    rows.append((plane.name, line.name, e.name,
                                 int(e.start_ns), int(e.duration_ns),
                                 of.get(e.name, "") if ops
                                 else _run_id(e)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in keep:
                        rows.append((plane.name, line.name, e.name,
                                     int(e.start_ns), int(e.duration_ns),
                                     ""))
                    elif e.name in (ENQUEUED, COMPLETED):
                        rows.append((plane.name, line.name, e.name,
                                     int(e.start_ns), int(e.duration_ns),
                                     _run_id(e)))
    return rows


# ---------------------------------------------------------- arithmetic
def leg_of(scope: str) -> str:
    """``jit(f)/update/scatter/scatter:`` -> ``scatter``: the last leg
    name on the path, the final component (the primitive) left out."""
    path = scope.split(":", 1)[0].split("/")[:-1]
    for part in reversed(path):
        if part in LEGS:
            return part
    return OTHER


def segments(intervals: list) -> list:
    """[(start, end, key)] -> disjoint [(t0, t1, key)] over the union of
    the intervals, each stretch owned by the covering interval that
    started last (the innermost, where they nest)."""
    ivs = sorted((iv for iv in intervals if iv[1] > iv[0]),
                 key=lambda iv: (iv[0], -iv[1]))
    out, active, i, t = [], [], 0, 0
    while i < len(ivs) or active:
        if not active:
            t = ivs[i][0]
        else:
            b = min(a[1] for a in active)
            if i < len(ivs):
                b = min(b, ivs[i][0])
            if b > t:
                key = active[-1][2]
                if out and out[-1][1] == t and out[-1][2] == key:
                    out[-1] = (out[-1][0], b, key)
                else:
                    out.append((t, b, key))
            t = b
            active = [a for a in active if a[1] > t]
        while i < len(ivs) and ivs[i][0] == t:
            active.append(ivs[i])
            i += 1
    return out


def _clip(intervals: list, lo: int, hi: int) -> list:
    out = []
    for a, b, k in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((a, b, k))
    return out


def _sum_by_key(segs: list) -> dict:
    out = {}
    for a, b, k in segs:
        out[k] = out.get(k, 0) + (b - a)
    return out


def _gaps(segs: list, lo: int, hi: int) -> list:
    """The stretches of [lo, hi] that no segment covers."""
    out, t = [], lo
    for a, b, _ in segs:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def clock_offset(rows: list):
    """Nanoseconds to add to a device plane's timestamps to put them on
    the host planes' clock -> (offset, lower bound, upper bound); the
    offset is 0 and a bound None where the trace lacks the runtime's
    events. A run starts no earlier than its enqueue and ends no later
    than its completion is seen."""
    runs, enq, comp = {}, {}, {}
    for plane, line, name, start, dur, scope in rows:
        if not scope.startswith("run_id="):
            continue
        if DEVICE_PLANE.match(plane):
            if line == MODULE_LINE:
                runs.setdefault(scope, (start, start + dur))
        elif name == ENQUEUED:
            enq[scope] = min(start, enq.get(scope, start))
        elif name == COMPLETED:
            comp[scope] = min(start, comp.get(scope, start))
    lower = [enq[r] - runs[r][0] for r in runs if r in enq]
    upper = [comp[r] - runs[r][1] for r in runs if r in comp]
    if not lower or not upper or max(lower) > min(upper):
        return 0, max(lower, default=None), min(upper, default=None)
    return (max(lower) + min(upper)) // 2, max(lower), min(upper)


def reduce(rows: list):
    """The three tables, in seconds, clipped to the marks; None where the
    rows hold no TPU plane or no pair of marks."""
    devices, spans, mark = {}, [], {}
    shift, low, high = clock_offset(rows)
    for plane, line, name, start, dur, scope in rows:
        if DEVICE_PLANE.match(plane):
            if line == OP_LINE:
                devices.setdefault(plane, []).append(
                    (start + shift, start + shift + dur, leg_of(scope)))
        elif name in MARKS:
            mark[name] = start
        elif name in SPANS:
            spans.append((start, start + dur, name))
    if not devices or len(mark) != 2 or mark[MARKS[1]] <= mark[MARKS[0]]:
        return None
    lo, hi = mark[MARKS[0]], mark[MARKS[1]]

    # the fullest device decides, as in tracered's idle gaps
    best = None
    for plane in sorted(devices):
        segs = segments(_clip(devices[plane], lo, hi))
        busy = sum(b - a for a, b, _ in segs)
        if best is None or busy > best[0]:
            best = (busy, segs)
    busy, op_segs = best
    legs = _sum_by_key(op_segs)

    spans = _clip(spans, lo, hi)
    span_s = {}
    for a, b, k in spans:
        span_s[k] = span_s.get(k, 0) + (b - a)
    span_segs = segments(spans)
    idle = {}
    idle_total = 0
    j = 0
    for g0, g1 in _gaps(op_segs, lo, hi):
        idle_total += g1 - g0
        covered = 0
        while j < len(span_segs) and span_segs[j][1] <= g0:
            j += 1
        k = j
        while k < len(span_segs) and span_segs[k][0] < g1:
            a, b, name = span_segs[k]
            ov = min(b, g1) - max(a, g0)
            idle[name] = idle.get(name, 0) + ov
            covered += ov
            k += 1
        if g1 - g0 > covered:
            idle[UNATTRIBUTED] = (idle.get(UNATTRIBUTED, 0)
                                  + (g1 - g0) - covered)

    def sec(d):
        return {k: v * 1e-9 for k, v in sorted(d.items(),
                                               key=lambda kv: -kv[1])}
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy * 1e-9,
            "legs_s": sec(legs), "spans_s": sec(span_s),
            "idle_s": sec(idle), "idle_total_s": idle_total * 1e-9,
            "scoped": any(k != OTHER for k in legs),
            "device_clock": {
                "shift_s": shift * 1e-9,
                "bounds_s": [None if b is None else b * 1e-9
                             for b in (low, high)]}}


# ------------------------------------------------------------- readers
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CACHE = {}


def tables(root: str = _ROOT):
    """``reduce`` of the live run's trace, read once a process and said
    once as an earlier line ``spans: {...}``; None where there is none."""
    path = find_run_trace(root)
    if path not in _CACHE:
        _CACHE.clear()
        _CACHE[path] = reduce(load_rows(path)) if path else None
        if _CACHE[path] is not None:
            print("spans: " + json.dumps(_CACHE[path]), flush=True)
    return _CACHE[path]


def leg_ms(ctx, leg: str):
    """Device milliseconds a step under scope ``leg``; None where the
    trace's operations carry no leg at all."""
    t = tables()
    if not t or not t["scoped"] or not ctx.get("steps"):
        return None
    return 1e3 * t["legs_s"].get(leg, 0.0) / ctx["steps"]


def leg_other_pct(ctx):
    t = tables()
    if not t or not t["scoped"] or not t["busy_s"]:
        return None
    step = sum(t["legs_s"].get(leg, 0.0) for leg in STEP_LEGS)
    return 100.0 * (t["busy_s"] - step) / t["busy_s"]


def _spanned(t) -> bool:
    return bool(t) and bool(t["spans_s"])


def idle_epoch_turn_ms(ctx):
    """Device-idle milliseconds an epoch inside ``epoch_turn``: under the
    span itself or, where a session's start or stop cut it, under one of
    its children."""
    t = tables()
    epochs = ctx["res"].get("window_epochs")
    if not _spanned(t) or not epochs:
        return None
    turn = sum(t["idle_s"].get(k, 0.0) for k in (TURN, *TURN_CHILDREN))
    return 1e3 * turn / epochs


def idle_unattributed_pct(ctx):
    t = tables()
    if not _spanned(t) or not t["idle_total_s"]:
        return None
    return 100.0 * t["idle_s"].get(UNATTRIBUTED, 0.0) / t["idle_total_s"]


def _stage(ctx, name: str):
    """The window's change of ``stage_seconds_total{stage=name}``; None
    where the program has no such stage, and where the run's trace has
    no TPU plane (a host time of a CPU run is no metric of a cell)."""
    if tables() is None:
        return None
    return ctx["res"].get("stages", {}).get(name)


def dispatch_host_us(ctx):
    s = _stage(ctx, "dispatch")
    if s is None or not ctx.get("steps"):
        return None
    return 1e6 * s / ctx["steps"]


def epoch_turn_ms(ctx):
    s = _stage(ctx, TURN)
    epochs = ctx["res"].get("window_epochs")
    if s is None or not epochs:
        return None
    return 1e3 * s / epochs


def window_compile_s(ctx):
    return _stage(ctx, "compile")
