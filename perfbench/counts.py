"""What the window did, by the program's own count — from the traced
run's ``.xplane.pb``.

The program counts its work where the work happens (the learner's
registry: steps and dispatches, row and chunk caps and what fills them,
bytes gathered and exchanged, compiles) and, at every training epoch's
end, says what the epoch did in one span of no length, ``epoch.counts``,
whose keyword arguments land in the live profiler session as the event's
stats: per-epoch differences, not totals. The record of the epoch that
opens the window is emitted before the session starts and is lost; the
record of every epoch that ends inside the window is kept, the closing
one included (it is emitted before the callback that stops the session).
So the records between the harness's two marks sum to exactly the
window's work, with no opening sample, and ``sut.drive`` (which
snapshots ``stage_seconds_total`` alone) need not know of them.

This module reads that file once more, beside ``spans.py`` (device time
by leg and idle by span) and ``tracered.py``, neither of which may be
edited by the PR that added this one. It keeps:

- the ``epoch.counts`` events that lie between the marks, with their
  stats, and sums them;
- the ``epoch_turn`` events that lie between the marks *whole*: the turn
  that opens the window carries the profiler's start (the harness's
  opening mark is taken inside the turn's ``epoch.callbacks`` child, so
  the counter ``epoch_turn_ms.replay`` reads 7-29 ms where a turn is
  1.5-1.8 ms long) and the turn that closes it is cut by the stop; the
  profiler records neither, and a clipped one would not count here.

``load_rows`` and ``reduce`` know no backend: a CPU profiler session
gives the same rows. The readers return ``None`` where there is nothing
to read: no record in the file (the parent of the PR that added them),
records whose examples do not sum to the window's rows (a record lost is
no number), no TPU plane in the trace (``spans.tables() is None``: a
count of a CPU run is no metric of a cell).
"""

from __future__ import annotations

import json

from perfbench import spans

RECORD = "epoch.counts"
# the record's arguments (difacto_tpu/obs/names.py COUNT_ARGS; a test
# pins the two lists to each other): summed over the window, and the
# model's gauges, of which the last record's stand
SUMMED = ("steps", "dispatches", "examples", "row_cap", "rows",
          "chunk_cap", "chunks", "own_cap", "own_rows", "gather_bytes",
          "exchange_bytes", "compile_s", "compiles")
LAST = ("nnz_w", "live_V")


# ------------------------------------------------------------ the file
def load_rows(path: str) -> list:
    """Rows [name, start_ns, dur_ns, stats] of the host planes' events
    that are a record, a turn or one of the two marks."""
    from jax.profiler import ProfileData
    keep = {RECORD, spans.TURN, *spans.MARKS}
    rows = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in keep:
                    rows.append([e.name, int(e.start_ns),
                                 int(e.duration_ns),
                                 dict(e.stats) if e.name == RECORD
                                 else {}])
    return rows


# ---------------------------------------------------------- arithmetic
def reduce(rows: list):
    """The window's sums; None where the rows hold no pair of marks."""
    mark = {name: start for name, start, _, _ in rows
            if name in spans.MARKS}
    if len(mark) != 2 or mark[spans.MARKS[1]] <= mark[spans.MARKS[0]]:
        return None
    lo, hi = mark[spans.MARKS[0]], mark[spans.MARKS[1]]
    inside = sorted((r for r in rows if r[0] not in spans.MARKS
                     and lo <= r[1] and r[1] + r[2] <= hi),
                    key=lambda r: r[1])
    records = [stats for name, _, _, stats in inside if name == RECORD]
    turns = [dur for name, _, dur, _ in inside if name == spans.TURN]
    return {
        "records": len(records),
        "epochs": [int(r.get("epoch", -1)) for r in records],
        "sums": {k: sum(r.get(k, 0) for r in records) for k in SUMMED},
        "last": {k: records[-1].get(k) for k in LAST} if records else {},
        "turns": len(turns),
        "turn_s": sum(turns) * 1e-9,
    }


# ------------------------------------------------------------- readers
_CACHE = {}


def tables(root: str = spans._ROOT):
    """``reduce`` of the live run's trace, read once a process and said
    once as an earlier line ``counts: {...}``; None where there is
    none."""
    path = spans.find_run_trace(root)
    if path not in _CACHE:
        _CACHE.clear()
        _CACHE[path] = reduce(load_rows(path)) if path else None
        if _CACHE[path] is not None:
            print("counts: " + json.dumps(_CACHE[path]), flush=True)
    return _CACHE[path]


def _window():
    """The run's table; None without a TPU plane."""
    if spans.tables() is None:
        return None
    return tables()


def _sums(ctx):
    """The window's sums where the records account for every row of it."""
    t = _window()
    if not t or not t["records"] \
            or t["sums"]["examples"] != ctx["res"].get("window_rows"):
        return None
    return t["sums"]


def _ratio(ctx, num: str, den: str, scale: float = 1.0):
    s = _sums(ctx)
    if s is None or not s[den]:
        return None
    return scale * s[num] / s[den]


def row_cap_fill_pct(ctx):
    """Distinct table rows of the window's steps over their row caps:
    the share of every cap-sized leg that is not padding."""
    return _ratio(ctx, "rows", "row_cap", 100.0)


def chunk_cap_fill_pct(ctx):
    """Chunks the steps' lanes need over their chunk caps: the fill of
    the backward's chunk gather and partial scatter."""
    return _ratio(ctx, "chunks", "chunk_cap", 100.0)


def steps_per_dispatch(ctx):
    """2 where every replayed step ran in a pair, 1 under a mesh; in
    between, staged batches below the schedule's final cap ran alone."""
    return _ratio(ctx, "steps", "dispatches")


def exchange_mb_per_step(ctx):
    """The all-reduce operand a step as the program moves it (the padded
    row cap), not the rows a chip must take in (``exchange.py``)."""
    return _ratio(ctx, "exchange_bytes", "steps", 1e-6)


def epoch_turn_span_ms(ctx):
    """Mean length of the turns that lie whole between the marks."""
    t = _window()
    if not t or not t["turns"]:
        return None
    return 1e3 * t["turn_s"] / t["turns"]


def idle_merge_stack_ms(ctx):
    """Device-idle milliseconds an epoch under ``merge.stack``, the eager
    stack before the epoch's fetch: most of the device's idle time."""
    t = spans.tables()
    epochs = ctx["res"].get("window_epochs")
    if not t or not t["spans_s"] or not epochs:
        return None
    return 1e3 * t["idle_s"].get("merge.stack", 0.0) / epochs
