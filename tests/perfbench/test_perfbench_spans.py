"""``perfbench/spans.py``: device time by leg, host time by program span,
device idle by the innermost program span — on a recorded list of rows
cut from a v5e trace of the program with its leg scopes and spans
(``data/span_rows_v64_replay.json.gz``) and on small made-up ones."""

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import perfbench_tiny as tiny  # noqa: E402,F401  (puts the root on the path)

from perfbench import run as R  # noqa: E402
from perfbench import spans  # noqa: E402

DEV, HOST = "/device:TPU:0", "/host:CPU"
OPEN, CLOSE = spans.MARKS


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(
            HERE, "data", "span_rows_v64_replay.json.gz"), "rt") as f:
        return [tuple(r) for r in json.load(f)["rows"]]


def op(start, dur, scope, name="%fusion.1 = f32[8]{0} fusion(...)"):
    return (DEV, spans.OP_LINE, name, start, dur, scope)


def host(name, start, dur):
    return (HOST, "python3", name, start, dur, "")


def marks(lo, hi):
    return [host(OPEN, lo, 10), host(CLOSE, hi, 10)]


# ------------------------------------------------------------------ legs
@pytest.mark.parametrize("scope,leg", [
    ("jit(packed_panel_train_chunked2)/update/scatter/scatter:", "scatter"),
    ("jit(packed_panel_train_chunked2)/gather/gather:", "gather"),
    # the primitive's own name is no leg: a program without scopes
    ("jit(packed_panel_train_chunked2)/gather:", "other"),
    ("jit(packed_panel_train_chunked2)/scatter:", "other"),
    ("jit(packed_panel_train_chunked2)/backward/reduce_sum:", "backward"),
    ("jit(f)/forward/jit(inner)/update/mul:", "update"),
    ("jit(evaluate)/evaluate/reduce_sum:", "evaluate"),
    ("jit(concatenate)/concatenate:", "other"),
    ("", "other"),
])
def test_leg_of(scope, leg):
    assert spans.leg_of(scope) == leg


def test_names_are_the_programs():
    from difacto_tpu.obs import names
    assert set(spans.LEGS) == set(names.LEGS)
    assert spans.OTHER not in names.LEGS
    assert spans.TURN == names.EPOCH_TURN
    assert spans.TURN_CHILDREN == names.TURN_CHILDREN
    program = {names.EPOCH, names.CONSUMER_DISPATCH, names.MERGE_STACK,
               names.COMPILE_PAIR, *names.TURN_CHILDREN,
               *(names.STAGE_SPAN.get(s, s) for s in names.STAGES)}
    assert set(spans.SPANS) <= program
    # what the readers' stages are called in the program
    for stage in ("dispatch", spans.TURN, "compile"):
        assert stage in names.STAGES


def test_segments_latest_start_owns():
    # b nests in a; c overlaps a's end without nesting
    segs = spans.segments([(0, 100, "a"), (20, 40, "b"), (90, 130, "c"),
                           (200, 200, "empty"), (300, 310, "d")])
    assert segs == [(0, 20, "a"), (20, 40, "b"), (40, 90, "a"),
                    (90, 130, "c"), (300, 310, "d")]
    assert spans.segments([]) == []


# ------------------------------------------------------ the recorded cut
def test_recorded_legs_sum_to_busy(recorded):
    red = spans.reduce(recorded)
    assert red["scoped"]
    assert sum(red["legs_s"].values()) == pytest.approx(red["busy_s"],
                                                        rel=1e-12)
    assert red["busy_s"] == pytest.approx(0.017315222, rel=1e-6)
    assert red["window_s"] == pytest.approx(0.037)
    # the epoch-end evaluate, the next step's gather, and the copies and
    # reshapes the compiler adds without a scope
    assert red["legs_s"]["evaluate"] == pytest.approx(0.006231716, rel=1e-6)
    assert red["legs_s"]["gather"] == pytest.approx(0.004301664, rel=1e-6)
    assert red["legs_s"]["other"] == pytest.approx(0.006781842, rel=1e-6)


def test_recorded_idle_goes_to_innermost_span(recorded):
    red = spans.reduce(recorded)
    assert sum(red["idle_s"].values()) == pytest.approx(
        red["idle_total_s"], rel=1e-12)
    assert red["idle_total_s"] == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)
    # the 14 ms stretch in which the host enqueues the eager stack's
    # programs lies inside ``epoch`` and under no child of it (this
    # trace predates the ``merge.stack`` span); the turn's children own
    # all of their own time, the device being idle throughout
    assert red["idle_s"]["epoch"] == pytest.approx(0.017250744, rel=1e-6)
    for child in ("epoch.merge", "replay.iter_parts", "epoch.callbacks"):
        assert red["idle_s"][child] == pytest.approx(
            red["spans_s"][child], rel=1e-9)
    assert red["idle_s"]["epoch_turn"] < red["spans_s"]["epoch_turn"]
    assert spans.UNATTRIBUTED not in red["idle_s"]


def test_recorded_clock_offset(recorded):
    shift, low, high = spans.clock_offset(recorded)
    assert (low, high) == (1455370, 1666322)
    assert shift == (low + high) // 2
    red = spans.reduce(recorded)
    assert red["device_clock"]["shift_s"] == pytest.approx(shift * 1e-9)
    # without the runtime's events nothing is shifted
    bare = [r for r in recorded
            if r[2] not in (spans.ENQUEUED, spans.COMPLETED)]
    assert spans.clock_offset(bare) == (0, None, None)
    # unshifted, the next step's first operation shows before the
    # ``dispatch`` span that enqueued it: no idle lands under dispatch
    assert spans.reduce(bare)["idle_s"].get("dispatch", 0.0) < 1e-6
    assert red["idle_s"]["dispatch"] > 5e-4


def test_events_outside_the_marks_do_not_count(recorded):
    inside = spans.reduce(recorded)
    wide = [r for r in recorded if r[2] not in spans.MARKS] \
        + marks(0, 45_000_000)
    whole = spans.reduce(wide)
    assert whole["busy_s"] > inside["busy_s"] + 0.004
    assert whole["window_s"] == pytest.approx(0.045)
    # the cut's last operations (the next step's forward) lie after the
    # planted closing mark
    assert "forward" in whole["legs_s"] and "forward" not in inside["legs_s"]


# ------------------------------------------------------------- made up
def test_gap_goes_to_the_innermost_span():
    rows = marks(0, 1000) + [
        op(0, 100, "jit(f)/gather/gather:"),
        op(100, 100, "jit(f)/update/scatter/scatter:"),
        # idle 200..600, then one more operation; idle 700..1000
        op(600, 100, "jit(f)/forward/mul:"),
        host("epoch", 0, 450),
        host("fetch_wait", 150, 100),        # 200..250 idle under it
        host("epoch_turn", 250, 330),        # begun inside ``epoch``
        host("epoch.merge", 260, 40),        # its child
        host("epoch", 500, 500),             # the next epoch
        host("dispatch", 560, 60),           # 560..600 idle under it
    ]
    red = spans.reduce(rows)
    ns = {k: round(v * 1e9) for k, v in red["idle_s"].items()}
    assert ns == {"fetch_wait": 50, "epoch_turn": 10 + 200,
                  "epoch.merge": 40, "epoch": 60 + 300, "dispatch": 40}
    assert round(red["idle_total_s"] * 1e9) == 700
    assert {k: round(v * 1e9) for k, v in red["legs_s"].items()} == {
        "gather": 100, "scatter": 100, "forward": 100}


def test_uncovered_idle_is_unattributed_and_nested_ops_sum_once():
    rows = marks(0, 1000) + [
        # a ``while`` around its body: the body's time is the body's
        op(0, 400, "", name="%while.1 = ..."),
        op(100, 200, "jit(f)/backward/gather:"),
        host("dispatch", 900, 50),
    ]
    red = spans.reduce(rows)
    assert round(red["busy_s"] * 1e9) == 400
    assert {k: round(v * 1e9) for k, v in red["legs_s"].items()} == {
        "other": 200, "backward": 200}
    assert round(red["idle_s"][spans.UNATTRIBUTED] * 1e9) == 550
    assert round(red["idle_s"]["dispatch"] * 1e9) == 50


def test_no_tpu_plane_or_no_marks_gives_none(recorded):
    host_only = [r for r in recorded if not r[0].startswith("/device")]
    assert spans.reduce(host_only) is None
    unmarked = [r for r in recorded if r[2] not in spans.MARKS]
    assert spans.reduce(unmarked) is None
    assert spans.reduce([]) is None


# ------------------------------------------------------------- the file
def test_event_scopes_reads_metadata_stats():
    space = spans._schema()()
    plane = space.planes.add(name=DEV)
    for key, name in ((3, "flops"), (7, "tf_op"), (9, "jit(f)/update/mul:")):
        e = plane.stat_metadata.add(key=key)
        e.value.id, e.value.name = key, name
    e = plane.event_metadata.add(key=1)
    e.value.id, e.value.name = 1, "%fusion.1 = ..."
    e.value.stats.add(metadata_id=3)
    e.value.stats.add(metadata_id=7, str_value="jit(f)/gather/gather:")
    e = plane.event_metadata.add(key=2)
    e.value.id, e.value.name = 2, "%fusion.2 = ..."
    e.value.stats.add(metadata_id=7, ref_value=9)      # an interned string
    space.planes.add(name=HOST).event_metadata.add(key=1)
    got = spans.event_scopes(space.SerializeToString())
    assert got == {DEV: {"%fusion.1 = ...": "jit(f)/gather/gather:",
                         "%fusion.2 = ...": "jit(f)/update/mul:"}}


def test_find_run_trace_takes_the_newest(tmp_path):
    assert spans.find_run_trace(str(tmp_path)) is None
    paths = []
    for i, run in enumerate(("run_a", "run_b")):
        d = tmp_path / ".perfbench_run" / run / "trace" / "plugins" \
            / "profile" / "2026_01_01"
        d.mkdir(parents=True)
        p = d / "host.xplane.pb"
        p.write_bytes(b"")
        os.utime(p, (1000 + i, 1000 + i))
        paths.append(str(p))
    assert spans.find_run_trace(str(tmp_path)) == paths[1]


# -------------------------------------------------------------- readers
@pytest.fixture
def reduced(recorded, monkeypatch):
    red = spans.reduce(recorded)
    monkeypatch.setattr(spans, "tables", lambda root=None: red)
    return red


def _reader(name):
    return R.load_reader(os.path.join(tiny.ROOT, "perfbench"), name)


def test_readers_on_the_recorded_cut(reduced):
    ctx = {"steps": 2.0, "res": {"window_epochs": 1, "stages": {
        "dispatch": 0.0005, "epoch_turn": 0.002, "compile": 0.0,
        "step": 0.1}}}
    assert _reader("leg_gather_ms.replay")(ctx) == pytest.approx(
        1e3 * 0.004301664 / 2, rel=1e-6)
    assert _reader("leg_scatter_ms.replay")(ctx) == 0.0
    for leg in ("forward", "backward", "update"):
        assert _reader(f"leg_{leg}_ms.replay")(ctx) == 0.0
    # evaluate and the unscoped copies: all that is not one of the five
    assert _reader("leg_other_pct.replay")(ctx) == pytest.approx(
        100 * (1 - 0.004301664 / 0.017315222), rel=1e-6)
    turn = sum(reduced["idle_s"].get(k, 0.0)
               for k in (spans.TURN, *spans.TURN_CHILDREN))
    assert _reader("idle_epoch_turn_ms.replay")(ctx) == pytest.approx(
        1e3 * turn)
    assert 1.0 < 1e3 * turn < 1.3
    assert _reader("idle_unattributed_pct.replay")(ctx) == 0.0
    assert _reader("dispatch_host_us.replay")(ctx) == pytest.approx(250.0)
    assert _reader("epoch_turn_ms.replay")(ctx) == pytest.approx(2.0)
    assert _reader("window_compile_s.replay")(ctx) == 0.0


NEW = ["leg_gather_ms.replay", "leg_forward_ms.replay",
       "leg_backward_ms.replay", "leg_update_ms.replay",
       "leg_scatter_ms.replay", "leg_other_pct.replay",
       "dispatch_host_us.replay", "epoch_turn_ms.replay",
       "idle_epoch_turn_ms.replay", "idle_unattributed_pct.replay",
       "window_compile_s.replay"]


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_on_a_program_without_names(name, recorded,
                                                         monkeypatch):
    """The parent of the PR that added legs, spans and stages: operations
    without a leg, no program span, the five old stage labels. Every new
    reader returns None and does not raise."""
    bare = [(p, ln, n, s, d, "") for p, ln, n, s, d, sc in recorded
            if p.startswith("/device") or n in spans.MARKS]
    red = spans.reduce(bare)
    assert red is not None and not red["scoped"] and not red["spans_s"]
    monkeypatch.setattr(spans, "tables", lambda root=None: red)
    ctx = {"steps": 2.0, "res": {"window_epochs": 1, "stages": {
        "parse": 0.0, "pack": 0.0, "ring_wait": 0.0, "transfer": 0.0,
        "step": 0.1}}}
    assert _reader(name)(ctx) is None
    # and with no trace at all
    monkeypatch.setattr(spans, "tables", lambda root=None: None)
    assert _reader(name)(ctx) is None


def test_benchmark_lists_the_new_metrics_without_workloads():
    """Added without a ``workloads`` list, for every cell that reports
    ``replay_ex_per_s``; the list now names those cells, the six there
    were, and a later cell joins behind them by its name."""
    b = tiny.bench()
    per_layer = {m["name"]: m for m in b["per_layer"]}
    six = [w["name"] for w in b["workloads"]][:6]
    for name in NEW:
        m = per_layer[name]
        assert m["workloads"][:6] == six
        assert m["moves"] == "replay_ex_per_s"
        assert os.path.exists(os.path.join(
            tiny.ROOT, "perfbench", "metrics", name + ".py"))
