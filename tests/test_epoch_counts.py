"""The epoch's record, ``epoch.counts``: one span a training epoch whose
arguments are what the epoch did (the learner's registry, differenced),
in both sinks (the span file and a live ``jax.profiler`` session); what
it costs with both off; and the compile listener's ``compiles_total{fn}``
with its INFO line."""

import glob
import json
import logging
import os
import threading
import time

import pytest

from conftest import write_uniform_libsvm
from difacto_tpu.obs import Registry, names, render_prometheus, trace
from difacto_tpu.obs.stage import watch_compiles

EPOCHS, ROWS, BATCH = 4, 128, 32
STEPS = ROWS // BATCH


def _learner(data, **args):
    from difacto_tpu.learners import Learner
    args = dict(dict(num_jobs_per_epoch=1, batch_size=BATCH, shuffle=0,
                     report_interval=0, stop_rel_objv=0,
                     producer_mode="thread", device_cache_mb=16, V_dim=4,
                     lr=0.1, l1=1e-4, hash_capacity=2048,
                     max_num_epochs=EPOCHS),
                data_in=data, **args)
    ln = Learner.create("sgd")
    assert ln.init([(k, str(v)) for k, v in args.items()]) == []
    return ln


def _series_now(ln) -> dict:
    """The registry's side of every argument, read as the record reads
    nothing: by name, from a snapshot."""
    c = ln.obs.snapshot()["counters"]
    train, path = (("job", "train"),), (("path", "train"),)
    return {
        "steps": c[names.STEPS][train],
        "dispatches": c[names.STEP_DISPATCHES][train],
        "examples": c["train_rows_total"][()],
        "row_cap": c[names.STEP_ROW_CAP][train],
        "rows": c[names.STEP_ROWS][train],
        "chunk_cap": c[names.STEP_CHUNK_CAP][train],
        "chunks": c[names.STEP_CHUNKS][train],
        "own_cap": c[names.STORE_OWNED_CAP][train],
        "own_rows": c[names.STORE_OWNED_ROWS][train],
        "gather_bytes": c["store_gather_bytes_total"][path],
        "exchange_bytes": c["store_exchange_bytes_total"][path],
        "compile_s": c[names.STAGE_METRIC][(("stage", names.COMPILE),)],
        "compiles": sum(c.get(names.COMPILES, {}).values()),
    }


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A tiny replayed run (epoch 0 streams and stages, the rest replay,
    in pairs once the pair program has compiled) under the span file's
    sink AND a profiler session of the test's own."""
    import jax
    tmp = tmp_path_factory.mktemp("epoch_counts")
    data = write_uniform_libsvm(str(tmp / "u.libsvm"), rows=ROWS)
    mpath = str(tmp / "m.jsonl")
    ln = _learner(data, metrics_path=mpath, metrics_interval_s=999)
    at_record, nrows = [], []
    record = ln._record_epoch_counts

    def spy(k):
        # no compile may land between this reading and the record's: the
        # pair program compiles in the background
        for t in threading.enumerate():
            if t.name == "pair-exec-compile":
                t.join(300)
        at_record.append(dict(
            _series_now(ln),
            nnz_w=ln.obs.value(names.MODEL_NNZ_W, job="train"),
            live_V=ln.obs.value(names.MODEL_LIVE_V, job="train")))
        record(k)

    ln._record_epoch_counts = spy
    ln.add_epoch_end_callback(lambda k, t, v: nrows.append(t.nrows))
    assert not trace.active()
    trace.drain_events()
    trace.start()
    jax.profiler.start_trace(str(tmp / "prof"))
    try:
        ln.run()
    finally:
        jax.profiler.stop_trace()
        trace.stop()
    in_file = [e for e in trace.drain_events()
               if e["name"] == names.EPOCH_COUNTS]
    from jax.profiler import ProfileData
    (pb,) = glob.glob(str(tmp / "prof" / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    in_session = []
    for plane in ProfileData.from_file(pb).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == names.EPOCH_COUNTS:
                    in_session.append((e.start_ns, dict(e.stats)))
    del ln._record_epoch_counts
    return {"learner": ln, "at_record": at_record, "nrows": nrows,
            "file": in_file,
            "session": [s for _, s in sorted(in_session)],
            "metrics_path": mpath}


def test_one_record_an_epoch_in_both_sinks(traced):
    assert [e["args"]["epoch"] for e in traced["file"]] == list(range(EPOCHS))
    assert [s["epoch"] for s in traced["session"]] == list(range(EPOCHS))
    keys = {"epoch", "job", *names.COUNT_ARGS}
    for ev, stats in zip(traced["file"], traced["session"]):
        assert keys <= set(ev["args"]) and set(stats) == keys
        assert [k for k in ev["args"] if k in names.COUNT_ARGS] \
            == list(names.COUNT_ARGS)
        # one interval, two sinks: the same arguments in both
        assert {k: ev["args"][k] for k in keys} == stats
    # a child of the open turn in the span file, and no child that
    # covers idle time: the list the benchmark pins stays as it was
    assert names.EPOCH_COUNTS not in names.TURN_CHILDREN


def test_arguments_are_the_registrys_differences(traced):
    prev = {}
    for ev, now, nrows in zip(traced["file"], traced["at_record"],
                              traced["nrows"]):
        a = ev["args"]
        for k in names.COUNT_ARGS[:-2]:
            want = now[k] - prev.get(k, 0.0)
            assert a[k] == pytest.approx(want, abs=1e-6), (a["epoch"], k)
            if k != "compile_s":
                assert isinstance(a[k], int)
        assert a["nnz_w"] == now["nnz_w"] and a["live_V"] == now["live_V"]
        assert a["examples"] == nrows == ROWS
        prev = now
    total = sum(e["args"]["examples"] for e in traced["file"])
    assert total == EPOCHS * ROWS
    assert sum(e["args"]["steps"] for e in traced["file"]) == EPOCHS * STEPS


def test_steps_a_dispatch_is_two_where_the_epoch_pairs(traced):
    first, last = traced["file"][0]["args"], traced["file"][-1]["args"]
    assert first["steps"] == first["dispatches"] == STEPS
    assert last["steps"] == STEPS and last["dispatches"] == STEPS // 2
    assert traced["learner"]._paired_dispatches > 0
    # epoch 0 compiles (the streamed step, evaluate, the eager stack),
    # a steady replay epoch nothing
    assert first["compiles"] > 0 and first["compile_s"] > 0
    assert last["compiles"] == 0 and last["compile_s"] == 0
    assert 0 < last["chunks"] <= last["chunk_cap"]
    assert 0 < last["rows"] <= last["row_cap"]
    assert last["gather_bytes"] > 0 and last["exchange_bytes"] == 0


def test_compiles_total_reaches_the_exports(traced):
    ln = traced["learner"]
    series = ln.obs.snapshot()["counters"][names.COMPILES]
    fns = {dict(k)["fn"] for k in series}
    step = [fn for fn in fns if "packed_panel_train" in fn]
    assert step, fns
    text = render_prometheus(ln.obs.snapshot())
    assert f'difacto_{names.COMPILES}{{fn="{step[0]}"}}' in text
    assert f"difacto_{names.STEPS}" in text
    assert f"difacto_{names.STEP_DISPATCHES}" in text
    with open(traced["metrics_path"]) as f:
        last = json.loads(f.readlines()[-1])
    flushed = last["metrics"]["counters"][names.COMPILES]
    assert any(step[0] in k for k in flushed)


def test_obs_report_prints_the_records_as_a_table(traced, capsys):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import obs_report
    obs_report.report_epoch_counts(traced["file"])
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("== epoch.counts")
    cols = out[1].split()
    rows = [line.split() for line in out[2:2 + EPOCHS]]
    assert [int(r[0]) for r in rows] == list(range(EPOCHS))
    first, last = (dict(zip(cols, r)) for r in (rows[0], rows[-1]))
    assert (first["/disp"], last["/disp"]) == ("1.00", "2.00")
    assert int(last["examples"]) == ROWS and last["own%"] == "-"
    assert 0 < float(last["row%"]) <= 100 and int(last["compiles"]) == 0
    # a trace without records prints no table
    obs_report.report_epoch_counts([{"name": "epoch", "args": {}}])
    assert capsys.readouterr().out == ""


def test_record_costs_little_with_both_sinks_off(traced):
    """Fifteen series read, two gauges, one inactive span: under 0.2 ms
    an epoch (measured here: ~20 us), bounded the way tests/test_obs.py
    bounds a span, by the minimum over short bursts."""
    ln = traced["learner"]
    assert not trace.active()
    best = float("inf")
    for _ in range(20):
        t0 = time.perf_counter()
        for _ in range(50):
            ln._record_epoch_counts(0)
        best = min(best, (time.perf_counter() - t0) / 50)
    assert best < 200e-6, best
    assert trace.drain_events() == []


def test_listener_names_a_forced_recompile(caplog):
    """``compiles_total{fn}`` and the INFO line name the function of
    every backend compile; a recompile of the same function counts
    again."""
    import jax
    import jax.numpy as jnp
    reg = Registry(enabled=True)
    watch_compiles(reg)

    def epoch_counts_probe(x):
        return x * 2.0 + 1.0

    fn = "jit(epoch_counts_probe)"
    with caplog.at_level(logging.INFO, logger="difacto_tpu.obs.stage"):
        jax.jit(epoch_counts_probe)(jnp.ones(3)).block_until_ready()
        assert reg.value(names.COMPILES, fn=fn) == 1
        before = reg.value(names.STAGE_METRIC, stage=names.COMPILE)
        assert before > 0
        jax.clear_caches()
        jax.jit(epoch_counts_probe)(jnp.ones(3)).block_until_ready()
    assert reg.value(names.COMPILES, fn=fn) == 2
    assert reg.value(names.STAGE_METRIC, stage=names.COMPILE) > before
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith(f"compiled {fn} in ")]
    assert len(lines) == 2 and lines[0].endswith(" s")
