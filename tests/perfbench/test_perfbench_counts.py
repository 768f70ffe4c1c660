"""``perfbench/counts.py``: the window's work by the program's own
``epoch.counts`` records and the whole ``epoch_turn`` spans between the
marks — on a recorded list of rows read from a v5e trace of
``fm_v64_avazu.replay_avazu`` (``data/counts_rows_avazu_replay.json``),
on made-up rows beside them, and on the file of a tiny learner run under
a CPU profiler session of the test's own (program -> profiler -> reader
with no chip)."""

import glob
import json
import os
import sys
import threading

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
import perfbench_tiny as tiny  # noqa: E402  (puts the root on the path)

from perfbench import counts  # noqa: E402
from perfbench import run as R  # noqa: E402
from perfbench import spans, sut  # noqa: E402

OPEN, CLOSE = spans.MARKS
FS4 = "fm_v64_criteo_fs4.replay_host4"
# name -> (layer, source, the cells it lists or None)
NEW = {
    "row_cap_fill_pct.replay": ("step", "program_counter", None),
    "chunk_cap_fill_pct.replay": ("step", "program_counter", None),
    "steps_per_dispatch.replay": ("learner, epoch engines",
                                  "program_counter", None),
    "exchange_mb_per_step.replay": ("store", "program_counter", [FS4]),
    "epoch_turn_span_ms.replay": ("learner, epoch engines",
                                  "device_trace", None),
    "idle_merge_stack_ms.replay": ("device", "device_trace", None),
}
FROM_RECORDS = list(NEW)[:4]
# the recorded window: 8 epochs of 32 steps of 65,536 rows, all paired
EPOCHS, STEPS, ROWS = 8, 256, 16777216


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data",
                           "counts_rows_avazu_replay.json")) as f:
        return json.load(f)["rows"]


def bounds(rows):
    mark = {r[0]: r[1] for r in rows if r[0] in spans.MARKS}
    return mark[OPEN], mark[CLOSE]


def record(start, **stats):
    return [counts.RECORD, start, 4000, dict(
        {k: 0 for k in counts.SUMMED + counts.LAST}, **stats)]


def _reader(name):
    return R.load_reader(os.path.join(tiny.ROOT, "perfbench"), name)


def _ctx(rows=ROWS, epochs=EPOCHS):
    return {"steps": rows / 65536, "res": {
        "window_rows": float(rows), "window_epochs": epochs, "stages": {}}}


@pytest.fixture
def on_tpu(recorded, monkeypatch):
    """A traced run on a chip whose file holds the recorded rows."""
    idle = {"idle_s": {"merge.stack": 0.132, "epoch_turn": 0.01},
            "spans_s": {"merge.stack": 0.14}}
    monkeypatch.setattr(spans, "tables", lambda root=None: idle)
    held = {"red": counts.reduce(recorded)}
    monkeypatch.setattr(counts, "tables", lambda root=None: held["red"])
    return held


# ------------------------------------------------------------ the names
def test_names_are_the_programs():
    from difacto_tpu.obs import names
    assert counts.RECORD == names.EPOCH_COUNTS
    assert counts.SUMMED + counts.LAST == names.COUNT_ARGS
    # the record is no child of the turn: the list that
    # ``test_perfbench_spans.py`` pins stays as it was
    assert spans.TURN_CHILDREN == names.TURN_CHILDREN
    assert counts.RECORD not in spans.SPANS


# ------------------------------------------------------ the recorded rows
def test_recorded_records_sum_to_the_window(recorded):
    red = counts.reduce(recorded)
    assert red["records"] == EPOCHS
    assert red["epochs"] == list(range(red["epochs"][0],
                                       red["epochs"][0] + EPOCHS))
    s = red["sums"]
    assert s["examples"] == ROWS and s["steps"] == STEPS
    assert s["dispatches"] == STEPS // 2
    assert s["row_cap"] == STEPS * 98304
    assert s["chunk_cap"] == STEPS * 114688
    assert 0.926 < s["rows"] / s["row_cap"] < 0.928
    assert 0.960 < s["chunks"] / s["chunk_cap"] < 0.963
    # a fused bf16 row of 256 lanes whole, pulled and pushed
    assert s["gather_bytes"] == s["row_cap"] * 512 * 2
    assert s["own_cap"] == s["exchange_bytes"] == 0      # no mesh
    assert s["compiles"] == 0 and s["compile_s"] == 0
    assert red["last"]["live_V"] > 0
    assert red["last"]["nnz_w"] > 64 * red["last"]["live_V"]
    # the turns between the window's epochs, whole: one fewer than epochs
    assert red["turns"] == EPOCHS - 1
    assert 1.0e-3 < red["turn_s"] / red["turns"] < 2.5e-3


def test_records_outside_the_marks_are_left_out(recorded):
    lo, hi = bounds(recorded)
    inside = counts.reduce(recorded)
    wide = recorded + [
        # the epoch that opens the window, had the session been live
        record(lo - 50_000, examples=2097152, steps=32, dispatches=16),
        # one emitted after the closing mark
        record(hi + 50_000, examples=2097152, steps=32, dispatches=16),
        # one that straddles the opening mark
        record(lo - 1000, examples=7)]
    assert counts.reduce(wide) == inside
    # and one more inside counts
    more = counts.reduce(recorded + [record(
        (lo + hi) // 2, examples=2097152, steps=32, dispatches=32)])
    assert more["records"] == EPOCHS + 1
    assert more["sums"]["examples"] == ROWS + 2097152
    assert more["sums"]["dispatches"] == STEPS // 2 + 32


def test_a_cut_turn_is_not_counted(recorded):
    lo, hi = bounds(recorded)
    inside = counts.reduce(recorded)
    cut = recorded + [
        [spans.TURN, lo - 1_000_000, 5_000_000, {}],    # carries the start
        [spans.TURN, hi - 1_000_000, 5_000_000, {}]]    # cut by the stop
    assert counts.reduce(cut) == inside
    whole = counts.reduce(recorded + [[spans.TURN, lo + 10, 3_000_000, {}]])
    assert whole["turns"] == inside["turns"] + 1
    assert whole["turn_s"] == pytest.approx(inside["turn_s"] + 3e-3)


def test_no_pair_of_marks_gives_none(recorded):
    assert counts.reduce([r for r in recorded if r[0] != CLOSE]) is None
    assert counts.reduce([r for r in recorded
                          if r[0] not in spans.MARKS]) is None
    assert counts.reduce([]) is None
    lo, hi = bounds(recorded)
    assert counts.reduce([[OPEN, hi, 10, {}], [CLOSE, lo, 10, {}]]) is None


# ------------------------------------- program -> profiler -> reader, CPU
def test_tiny_learner_under_a_cpu_session(tmp_path):
    """The learner's records, through a profiler session and this
    module's own functions, with no backend asked: between the test's
    two marks the records sum to the rows trained there, and a replayed
    epoch runs two steps a dispatch."""
    import jax
    from conftest import write_uniform_libsvm
    from difacto_tpu.learners import Learner
    rows, batch, first, last = 128, 32, 2, 5
    data = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=rows)
    ln = Learner.create("sgd")
    assert ln.init([(k, str(v)) for k, v in dict(
        data_in=data, num_jobs_per_epoch=1, batch_size=batch, shuffle=0,
        report_interval=0, stop_rel_objv=0, producer_mode="thread",
        device_cache_mb=16, V_dim=4, lr=0.1, l1=1e-4, hash_capacity=2048,
        max_num_epochs=last + 2).items()]) == []
    trained = []

    def on_end(k, train_prog, _val):
        for t in threading.enumerate():
            if t.name == "pair-exec-compile":
                t.join(300)
        if first < k <= last:
            trained.append(train_prog.nrows)
        if k == first:
            jax.profiler.start_trace(str(tmp_path / "prof"))
            sut._mark(OPEN)
        elif k == last:
            sut._mark(CLOSE)
            jax.profiler.stop_trace()

    ln.add_epoch_end_callback(on_end)
    ln.run()
    (pb,) = glob.glob(str(tmp_path / "prof" / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    red = counts.reduce(counts.load_rows(pb))
    assert red["epochs"] == list(range(first + 1, last + 1))
    assert red["sums"]["examples"] == sum(trained) == (last - first) * rows
    assert red["sums"]["steps"] == (last - first) * rows // batch
    assert red["sums"]["steps"] == 2 * red["sums"]["dispatches"]
    assert 0 < red["sums"]["rows"] <= red["sums"]["row_cap"]
    assert red["sums"]["compiles"] == 0
    # the turn that carries the session's start and the one its stop
    # cuts are not in the file
    assert red["turns"] == last - first - 1


# -------------------------------------------------------------- readers
def test_readers_on_the_recorded_rows(on_tpu):
    s = on_tpu["red"]["sums"]
    ctx = _ctx()
    assert _reader("row_cap_fill_pct.replay")(ctx) == pytest.approx(
        100.0 * s["rows"] / (STEPS * 98304))
    assert _reader("chunk_cap_fill_pct.replay")(ctx) == pytest.approx(
        100.0 * s["chunks"] / (STEPS * 114688))
    assert _reader("steps_per_dispatch.replay")(ctx) == 2.0
    assert _reader("exchange_mb_per_step.replay")(ctx) == 0.0
    assert _reader("epoch_turn_span_ms.replay")(ctx) == pytest.approx(
        1e3 * on_tpu["red"]["turn_s"] / (EPOCHS - 1))
    assert _reader("idle_merge_stack_ms.replay")(ctx) == pytest.approx(
        132.0 / EPOCHS)


def test_exchange_reads_the_operand_a_step(on_tpu):
    """The four-chip cell's record: row cap x 256 lanes x 2 B a step."""
    red = on_tpu["red"]
    red["sums"] = dict(red["sums"], exchange_bytes=STEPS * 294912 * 512,
                       dispatches=STEPS)
    ctx = _ctx()
    assert _reader("exchange_mb_per_step.replay")(ctx) == pytest.approx(
        150.994944)
    assert _reader("steps_per_dispatch.replay")(ctx) == 1.0


@pytest.mark.parametrize("name", FROM_RECORDS)
def test_a_lost_record_is_no_number(name, on_tpu, recorded):
    """The records' examples must sum to the window's rows: with one
    record dropped, or one too many, every reader of the sums finds
    nothing; the readers of spans still read."""
    ctx = _ctx()
    assert _reader(name)(ctx) is not None
    first = next(i for i, r in enumerate(recorded) if r[0] == counts.RECORD)
    on_tpu["red"] = counts.reduce(recorded[:first] + recorded[first + 1:])
    assert on_tpu["red"]["records"] == EPOCHS - 1
    assert _reader(name)(ctx) is None
    assert _reader("epoch_turn_span_ms.replay")(ctx) is not None
    assert _reader("idle_merge_stack_ms.replay")(ctx) is not None


def test_the_parent_reports_the_span_readers_alone(on_tpu, recorded):
    """A program without the record (the parent of the PR that added
    it): the file has turns and marks, no ``epoch.counts``."""
    on_tpu["red"] = counts.reduce([r for r in recorded
                                   if r[0] != counts.RECORD])
    assert on_tpu["red"]["records"] == 0 and on_tpu["red"]["last"] == {}
    ctx = _ctx()
    for name in FROM_RECORDS:
        assert _reader(name)(ctx) is None
    assert _reader("epoch_turn_span_ms.replay")(ctx) > 0
    assert _reader("idle_merge_stack_ms.replay")(ctx) > 0


@pytest.mark.parametrize("name", list(NEW))
def test_reader_finds_nothing_without_a_tpu_plane(name, monkeypatch):
    """A CPU traced run (``spans.tables() is None``) reports none of the
    new metrics: ``tiny.run(..., trace=True)`` keeps reporting
    ``setup_compile_s`` and ``setup_stage_s`` alone, and the file is not
    even read."""
    monkeypatch.setattr(spans, "tables", lambda root=None: None)

    def never(root=None):
        raise AssertionError("the trace was read without a TPU plane")

    monkeypatch.setattr(counts, "tables", never)
    assert _reader(name)(_ctx()) is None
    # and with a TPU plane but no trace of the run at all
    monkeypatch.setattr(spans, "tables", lambda root=None: {
        "idle_s": {}, "spans_s": {}})
    monkeypatch.setattr(counts, "tables", lambda root=None: None)
    assert _reader(name)(_ctx()) is None


def test_tables_reads_the_file_once_and_says_the_line(recorded, tmp_path,
                                                      monkeypatch, capsys):
    d = tmp_path / ".perfbench_run" / "run_x" / "trace" / "plugins" \
        / "profile" / "2026_10_05"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    loads = []

    def load(path):
        loads.append(path)
        return recorded

    monkeypatch.setattr(counts, "load_rows", load)
    monkeypatch.setattr(counts, "_CACHE", {})
    red = counts.tables(str(tmp_path))
    assert counts.tables(str(tmp_path)) is red and len(loads) == 1
    out = capsys.readouterr().out.splitlines()
    assert len(out) == 1 and out[0].startswith("counts: ")
    assert json.loads(out[0][len("counts: "):]) == red
    assert counts.tables(str(tmp_path / "nowhere")) is None


# ---------------------------------------------------------- the benchmark
@pytest.mark.parametrize("name", list(NEW))
def test_benchmark_lists_the_metric_with_its_reader(name):
    layer, source, cells = NEW[name]
    per_layer = tiny.bench()["per_layer"]
    m = next(m for m in per_layer if m["name"] == name)
    assert m["moves"] == "replay_ex_per_s"
    assert (m["layer"], m["source"]) == (layer, source)
    assert m.get("workloads") == cells
    assert os.path.exists(os.path.join(
        tiny.ROOT, "perfbench", "metrics", name + ".py"))
    assert callable(_reader(name))
    # appended, in this order, behind the nineteen that were there
    names = [x["name"] for x in per_layer]
    assert [n for n in names if n in NEW] == list(NEW)
    assert min(names.index(n) for n in NEW) >= 19
