"""The harness's own functions driven at a tiny size on the CPU: set-up,
window, comparison and the last line's shape; the lower-precision control
and the planted faults come out as not correct; the command without a
TPU prints no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import perfbench_tiny as tiny

E2E_KEYS = {"correct", "attempted", "failed", "metrics", "device",
            "checked"}


@pytest.fixture()
def fused_root(tmp_path):
    return tiny.make_root(str(tmp_path))


class Root(str):
    """A tiny benchmark's directory, and the layout it was made for."""
    flat = False
    limits = tiny.TINY_LIMITS


@pytest.fixture(params=["fused", "flat"])
def root(request, tmp_path):
    """The flagship's replay cell at a tiny size, in each of the table's
    layouts: fused float32 rows of V_dim 8, and the flat V_dim = 0 table
    (l1 logistic regression)."""
    if request.param == "fused":
        return Root(tiny.make_root(str(tmp_path)))
    root = Root(tiny.make_root(str(tmp_path), **tiny.FLAT))
    root.flat, root.limits = True, tiny.FLAT_LIMITS
    return root


def test_replay_cell_runs_and_is_correct(root):
    res, lines = tiny.run(root, seconds=0.2)
    assert set(res) == E2E_KEYS and list(res)[-1] == "checked"
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"replay_ex_per_s", "setup_s"}
    assert res["metrics"]["replay_ex_per_s"]["unit"] == "examples/s"
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    win = json.loads(lines["window"])
    # whole epochs of full steps between two marks, all time counted
    assert win["rows"] == win["epochs"] * 8 * 64
    assert res["attempted"] == win["steps"] == win["rows"] / 64
    assert win["seconds"] >= 0.2 and win["warm_epochs"] >= 1
    assert res["metrics"]["replay_ex_per_s"]["value"] == pytest.approx(
        win["rows"] / win["seconds"])
    assert win["device_cache"]["3"]["complete"]
    assert win["paired_dispatches"] > 0          # replay ran in pairs
    ref = json.loads(lines["reference"])
    assert ref["program"]["Vg_after_step1"] == 0.0
    assert ref["program"]["nnz_w"] == ref["reference"]["nnz_w"] > 0
    # exactly the layout's numbers: the first steps' (ten of a fused row,
    # nine of the flat table), the pair executable's, the rows
    assert set(res["checked"]) == set(root.limits)
    assert any(n.endswith("_V") for n in res["checked"]) is not root.flat
    for name, c in res["checked"].items():
        assert c["value"] <= c["limit"] == root.limits[name], name
    # every leaf of the state: 17 bytes a flat row, 128 lanes a fused one
    assert win["table_bytes"] == 4096 * (17 if root.flat else 128 * 4)
    assert len(ref["pair_loss"]["program"]) == 2
    json.dumps(res)                              # one JSON line


def test_traced_run_reports_per_layer_metrics_only(root):
    res, lines = tiny.run(root, seconds=5, trace=True)
    assert list(res)[-1] == "checked" and "breakdown" in res
    # no TPU plane in a CPU trace: the device readers find nothing and
    # their metrics are left out, never reported as 0
    assert set(res["metrics"]) == {"setup_compile_s", "setup_stage_s"}
    assert json.loads(lines["window"])["seconds"] < 5   # trace_seconds
    assert res["correct"] is True


@pytest.mark.parametrize("seed", [1, 2**31 + 9])
def test_same_seed_same_comparison(root, seed):
    a, _ = tiny.run(root, seed=seed)
    b, _ = tiny.run(root, seed=seed)
    assert a["correct"] and b["correct"]
    # the first steps are the seed's alone; the pair is read in whichever
    # epoch follows its compile, so its numbers may differ from run to run
    for name in a["checked"]:
        if not name.startswith("pair_"):
            assert a["checked"][name] == b["checked"][name], name


def test_lower_precision_control_is_not_correct(fused_root):
    """The float32 configuration run with bfloat16 rows."""
    res, _ = tiny.run(fused_root, override={"V_dtype": "bfloat16"})
    assert res["correct"] is False
    bad = {n for n, c in res["checked"].items() if c["value"] > c["limit"]}
    assert {"change_V", "keep_V", "round_V"} <= bad


def test_int8_control_of_bf16_rows_is_not_correct(tmp_path):
    lim = dict(tiny.TINY_LIMITS, loss2=1e-3, loss3=1e-3, grad_V=1e-3,
               change_w=1e-3, change_V=1e-2, round_V=1e-2, round_Vg=1e-2,
               pair_loss1=5e-3, pair_loss2=5e-3, pair_change_w=5e-3,
               pair_change_V=5e-2, pair_round_V=1e-2)
    root = tiny.make_root(str(tmp_path), V_dtype="bfloat16", limits=lim)
    assert tiny.run(root)[0]["correct"] is True
    res, _ = tiny.run(root, override={"slot_dtype": "int8"})
    assert res["correct"] is False
    # 8-bit rows round without bias: only a row-wise number sees them,
    # and the rows that no step updated are exact in bfloat16
    bad = {n for n, c in res["checked"].items() if c["value"] > c["limit"]}
    # (the pair's reference starts from the rows as the program held
    # them, so the pair's numbers cannot see how they are stored)
    assert bad == {"keep_V"}


def test_l1_left_out_fails_zero_w(tmp_path):
    """The flat table's control: the program run without the soft
    threshold, so that no weight a batch touched is exactly 0."""
    root = tiny.make_root(str(tmp_path), **tiny.FLAT)
    res, lines = tiny.run(root, override={"l1": 0})
    assert res["correct"] is False
    assert res["checked"]["zero_w"]["value"] > 0.05
    assert res["checked"]["loss1"]["value"] == 0.0    # every w starts at 0
    ref = json.loads(lines["reference"])
    assert ref["program"]["nnz_w"] > ref["reference"]["nnz_w"] > 0


@pytest.mark.parametrize("limits, bad", [
    ({k: v for k, v in tiny.FLAT_LIMITS.items() if k != "zero_w"},
     {"zero_w"}),
    (dict(tiny.FLAT_LIMITS, keep_V=1e-5), {"keep_V"}),
    (tiny.TINY_LIMITS, set(tiny.TINY_LIMITS) ^ set(tiny.FLAT_LIMITS)),
], ids=["lacks_a_flat_number", "names_a_V_number", "a_fused_cells_file"])
def test_flat_cell_needs_the_flat_limits(tmp_path, limits, bad):
    root = tiny.make_root(str(tmp_path), **dict(tiny.FLAT, limits=limits))
    res, _ = tiny.run(root)
    assert res["correct"] is False
    failed = {n for n, c in res["checked"].items()
              if c["limit"] is None or c["value"] == "inf"}
    assert failed == bad


def test_calibrate_reads_the_layouts_faults(root, monkeypatch):
    """``calibrate.py --faults`` at the tiny size: the sound numbers, and
    each fault planted in the reference reads far from them; the soft
    threshold left out is the flat table's alone."""
    from perfbench import calibrate, sut
    # the tool reads the repository's own files: hand it the tiny ones
    monkeypatch.setattr(calibrate, "ROOT", str(root))
    row = calibrate.reading(tiny.bench(), "fm_v64_criteo.replay", 7, None,
                            "pair", True, False)
    assert row["correct"] is True
    assert set(row["numbers"]) | {"epoch_rows"} == set(root.limits)
    assert max(row["numbers"].values()) < 1e-5
    faults = row["faults"]
    assert set(faults) == {"half_batch", "stale"} | (
        {"no_l1"} if root.flat else set())
    assert set(faults) <= set(sut.FAULTS)
    assert faults["half_batch"]["loss1"] == pytest.approx(0.5, abs=0.05)
    assert faults["half_batch"]["pair_loss1"] == pytest.approx(0.5,
                                                               abs=0.05)
    assert faults["stale"]["pair_loss1"] == 0.0
    assert faults["stale"]["pair_change_w"] > 1e-3
    assert set(faults["stale"]) == {n for n in root.limits
                                    if n.startswith("pair_")}
    if root.flat:
        assert faults["no_l1"]["zero_w"] > 0.05
        assert faults["no_l1"]["loss1"] == 0.0


def _broken_step(monkeypatch, breaker):
    """Break the timed path underneath: every train step that the learner
    builds goes through ``breaker(train_step)``."""
    import difacto_tpu.step as step_mod
    real = step_mod.make_step_fns

    def make(*a, **kw):
        fwd, train, ev = real(*a, **kw)
        return fwd, breaker(train), ev

    monkeypatch.setattr(step_mod, "make_step_fns", make)


def test_fault_state_unchanged_is_not_correct(root, monkeypatch):
    def breaker(train):
        def step(state, batch, slots):
            _, objv, auc = train(state, batch, slots)
            return state, objv, auc
        return step
    _broken_step(monkeypatch, breaker)
    res, _ = tiny.run(root)
    assert res["correct"] is False
    # nothing moved: a gap of norms of 1 on every leaf
    assert res["checked"]["change_w"]["value"] == pytest.approx(1.0)
    assert res["checked"]["grad_w"]["value"] == pytest.approx(1.0)


def test_fault_half_the_batch_left_out_is_not_correct(root, monkeypatch):
    def breaker(train):
        def step(state, batch, slots):
            import jax.numpy as jnp
            keep = (jnp.arange(batch.row_mask.shape[0]) % 2).astype(
                batch.row_mask.dtype)
            return train(state, batch._replace(
                row_mask=batch.row_mask * keep), slots)
        return step
    _broken_step(monkeypatch, breaker)
    res, _ = tiny.run(root)
    assert res["correct"] is False
    assert res["checked"]["loss1"]["value"] == pytest.approx(0.5)
    assert res["checked"]["grad_w"]["value"] > 0.1


def test_fault_a_label_altered_is_not_correct(root, monkeypatch):
    """An answer altered where it is produced: the step sees every label
    flipped."""
    def breaker(train):
        def step(state, batch, slots):
            return train(state, batch._replace(labels=1 - batch.labels),
                         slots)
        return step
    _broken_step(monkeypatch, breaker)
    res, _ = tiny.run(root)
    assert res["correct"] is False


def _broken_pair(monkeypatch, breaker):
    """Break the pair-replay program alone, the one a replay window
    times: the function it is jitted from goes through ``breaker``; every
    other program is as it was."""
    from difacto_tpu.utils import jaxtrace
    real = jaxtrace.jit

    def jit(fn, *a, **kw):
        if getattr(fn, "__name__", "") == "packed_panel_train_chunked2":
            fn = breaker(fn)
        return real(fn, *a, **kw)

    monkeypatch.setattr(jaxtrace, "jit", jit)


def _first_batch_twice(pair):
    def broken(state, pa, pb, *statics):
        return pair(state, pa, pa, *statics)
    return broken


def _write_back_dropped(pair):
    def broken(state, pa, pb, *statics):
        _, o1, a1, o2, a2 = pair(state, pa, pb, *statics)
        return state, o1, a1, o2, a2
    return broken


@pytest.mark.parametrize("breaker, sees", [
    (_first_batch_twice, {"pair_loss2", "pair_change_w"}),
    (_write_back_dropped, {"pair_change_w", "pair_change_V"}),
], ids=["first_batch_twice", "write_back_dropped"])
def test_fault_in_the_pair_program_alone_is_not_correct(
        root, monkeypatch, breaker, sees):
    sees = sees & set(root.limits)
    _broken_pair(monkeypatch, breaker)
    res, lines = tiny.run(root, seconds=0.2)
    assert json.loads(lines["window"])["paired_dispatches"] > 0
    assert res["correct"] is False
    bad = {n for n, c in res["checked"].items() if c["value"] > c["limit"]}
    # the first steps ran the sound single-batch program: only the
    # pair's numbers see the fault
    assert bad and all(n.startswith("pair_") for n in bad)
    assert sees <= bad
    if breaker is _write_back_dropped:
        assert res["checked"]["pair_change_w"]["value"] == \
            pytest.approx(1.0)


def test_fault_a_batch_skipped_in_replay_is_not_correct(root, monkeypatch):
    """An epoch of the window that runs a batch short."""
    from difacto_tpu.learners import sgd

    real = sgd._DeviceBatchCache.iter_parts

    def short(self, *a, **kw):
        items = list(real(self, *a, **kw))
        return iter(items[:-2])

    monkeypatch.setattr(sgd._DeviceBatchCache, "iter_parts", short)
    res, _ = tiny.run(root, seconds=0.2)
    assert res["correct"] is False
    assert res["checked"]["epoch_rows"]["value"] == pytest.approx(0.25)


def _command(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "fm_v64_criteo.replay", "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_without_a_tpu_prints_no_result():
    p = _command(tiny.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_command_needs_the_program(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    directories: no program, no result."""
    for rel in tiny.bench()["paths"]:
        shutil.copytree(os.path.join(tiny.ROOT, rel),
                        os.path.join(tmp_path, rel),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    p = _command(str(tmp_path))
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
