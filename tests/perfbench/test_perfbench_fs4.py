"""The four-chip cell ``fm_v64_criteo_fs4.replay_host4``: its traffic
file, its run at a tiny size on four virtual devices, its planted faults,
and the arithmetic of the exchange's metrics."""

import json
import os

import pytest

import perfbench_tiny as tiny
from perfbench import calibrate_fs, exchange, work

CELL = "fm_v64_criteo_fs4.replay_host4"
PERF = os.path.join(tiny.ROOT, "perfbench")


def _json(*rel):
    with open(os.path.join(PERF, *rel)) as f:
        return json.load(f)


# -------------------------------------------------------- the data files
def test_traffic_differs_from_replay_in_the_budget_alone():
    a, b = _json("traffic", "replay.json"), _json("traffic",
                                                  "replay_host4.json")
    assert set(a) == set(b)
    assert {k for k in a if a[k] != b[k]} == {"about", "learner"}
    la, lb = a["learner"], b["learner"]
    assert {k for k in la if la[k] != lb[k]} == {"device_cache_mb"}
    # the per-host budget: the one-chip cells' budget a chip, four chips
    assert lb["device_cache_mb"] == 4 * la["device_cache_mb"] == 16384


def test_cell_is_declared_by_additions():
    """The four-chip cell, its configuration and its metrics are entries
    of their lists, wherever later cells put them."""
    b = tiny.bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert [w["name"] for w in b["workloads"] if w["chips"] == 4] == [CELL]
    assert cell["traffic"] == "replay_host4"
    assert cell["config"] == "fm_v64_criteo_fs4"
    entry = next(c for c in b["configs"] if c["name"] == cell["config"])
    cfg = _json("configs", "fm_v64_criteo_fs4.json")
    assert entry["source"] == cfg["about"]["source"]
    assert cfg["hash_capacity"] == 2 ** 25 and cfg["mesh_fs"] == 4
    rate = next(m for m in b["end_to_end"] if m["name"] == "replay_ex_per_s")
    assert CELL in rate["workloads"]
    new = [m for m in b["per_layer"] if m["name"] in (
        "collective_pct.replay", "exchange_roofline.replay")]
    assert len(new) == 2 and all(m.get("workloads") == [CELL] for m in new)
    assert all(m["layer"] == "store" and m["moves"] == "replay_ex_per_s"
               and m["unit"] == "%" for m in new)
    # no pair program under a mesh: a limit without its number fails
    limits = _json("limits", CELL + ".json")
    assert not any(k.startswith("pair_") for k in limits)
    assert limits["epoch_rows"] == 0


# ------------------------------------------------- the cell, at tiny size
@pytest.fixture()
def root(tmp_path):
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    return tiny.make_root(str(tmp_path), config="fm_v64_criteo_fs4",
                          capacity=4096, mesh_fs=4,
                          limits=tiny.FIRST_LIMITS)


def test_cell_runs_correct_and_replays_on_four_virtual_devices(root):
    res, lines = tiny.run(root, workload=CELL, seconds=0.2)
    assert res["correct"] is True, res["checked"]
    assert set(res["checked"]) == set(tiny.FIRST_LIMITS)
    assert set(res["metrics"]) == {"replay_ex_per_s", "setup_s"}
    win = json.loads(lines["window"])
    assert win["device_cache"]["3"]["complete"] and win["epochs"] >= 1
    assert win["paired_dispatches"] == 0      # one batch a dispatch
    assert win["table_rows"] == 4096


def test_traced_run_leaves_the_device_metrics_out_on_the_cpu(root):
    res, _ = tiny.run(root, workload=CELL, seconds=5, trace=True)
    assert res["correct"] is True
    # a CPU trace has no TPU plane: nothing to read, nothing reported
    assert set(res["metrics"]) == {"setup_compile_s", "setup_stage_s"}


def _broken_step(monkeypatch, breaker):
    import difacto_tpu.step as step_mod
    real = step_mod.make_step_fns

    def make(*a, **kw):
        fwd, train, ev = real(*a, **kw)
        return fwd, breaker(train), ev

    monkeypatch.setattr(step_mod, "make_step_fns", make)


def _half_batch(train):
    def step(state, batch, slots):
        import jax.numpy as jnp
        keep = (jnp.arange(batch.row_mask.shape[0]) % 2).astype(
            batch.row_mask.dtype)
        return train(state, batch._replace(row_mask=batch.row_mask * keep),
                     slots)
    return step


def _shard_left_out(train):
    """The gather reads zeros for the rows of one key-range shard: their
    slots are pushed out of range, where the gather fills with zeros (and
    the scatter drops them)."""
    def step(state, batch, slots):
        import jax.numpy as jnp
        cap = state.VVg.shape[0]
        lo = cap // 4
        out = (slots >= lo) & (slots < 2 * lo)
        return train(state, batch, jnp.where(out, cap + slots, slots))
    return step


@pytest.mark.parametrize("breaker, sees", [
    (_half_batch, {"loss1", "grad_w", "change_w"}),
    (_shard_left_out, {"grad_w", "change_w", "change_V"}),
], ids=["half_batch", "shard_left_out"])
def test_planted_fault_is_not_correct(root, monkeypatch, breaker, sees):
    _broken_step(monkeypatch, breaker)
    res, _ = tiny.run(root, workload=CELL)
    assert res["correct"] is False
    bad = {n for n, c in res["checked"].items()
           if c["value"] == "inf" or c["value"] > c["limit"]}
    assert sees <= bad, res["checked"]


def test_shard_out_fault_of_the_reference_reads_apart(root):
    """``calibrate_fs.py --faults`` at the tiny size: both planted faults
    read far from the sound numbers."""
    import shutil
    b = tiny.bench()
    # the tool reads the repository's own files: hand it the tiny ones
    real = calibrate_fs.ROOT
    calibrate_fs.ROOT = root
    try:
        shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), root)
        row = calibrate_fs.reading(b, CELL, 7, None, True, False)
    finally:
        calibrate_fs.ROOT = real
    assert row["correct"] is True
    assert max(row["numbers"].values()) < 1e-5
    assert row["faults"]["half_batch"]["loss1"] == pytest.approx(0.5,
                                                                 abs=0.02)
    shard = row["faults"]["shard_out"]
    assert shard["keep_V"] > 0.3 and shard["loss1"] == 0.0


# ------------------------------------------------ the exchange's metrics
def test_step_exchange_hand_counted():
    # 1000 distinct features over 4 shards, V_dim 4 in 2-byte items:
    # a chip owns a quarter and must take in the other 750 rows
    need = exchange.step_exchange(u=1000, V_dim=4, itemsize=2, fs=4)
    assert need["bytes_per_chip"] == 750 * (2 * 4 * 2 + 4 * 4)
    assert exchange.step_exchange(1000, 4, 2, 1)["bytes_per_chip"] == 0
    # the row is work.py's row: reading it once is half of what a step
    # reads and writes
    w = work.step_work(1000, 0, 0, 4, 2)
    assert exchange.step_exchange(1000, 4, 2, 2)["bytes_per_chip"] \
        == w["bytes"] / 4


def test_ici_peak_table():
    assert exchange.load_ici_peak("TPU v5 lite") == 200e9   # 1600 Gbit/s
    for kind in ("TPU v9 imaginary", "_source"):
        with pytest.raises(KeyError):
            exchange.load_ici_peak(kind)


def test_uniq_comes_back_from_the_least_time():
    peaks = work.load_peaks("TPU v5 lite")
    for u in (283_600.0, 279_123.5):
        w = work.step_work(u, 65536, 65536 * 39, 64, 2)
        least = work.least_seconds(w, peaks, 4)
        assert exchange.uniq_of(least, peaks, 4, 65536, 65536 * 39, 64,
                                2) == pytest.approx(u, rel=1e-9)


def _ctx(collective_s, busy_s, steps=100, u=283_600.0):
    peaks = work.load_peaks("TPU v5 lite")
    w = work.step_work(u, 65536, 65536 * 39, 64, 2)
    return {"trace": {"collective_s_fullest": collective_s,
                      "busy_s_fullest": busy_s, "busy_s": busy_s,
                      "window_s": busy_s * 1.02},
            "least": work.least_seconds(w, peaks, 4), "steps": steps,
            "chips": 4, "res": {"window_rows": 65536.0 * steps}}


def test_readers_on_a_synthetic_trace(monkeypatch):
    """The cell's own sizes, a trace made by hand: 2.66 ms of all-reduce
    in a 59.95 ms step (the ledger's PR 27 line)."""
    from perfbench import run as R
    monkeypatch.setattr(exchange, "device_kind", lambda: "TPU v5 lite")
    ctx = _ctx(collective_s=0.266, busy_s=5.995)
    read = {m: R.load_reader(PERF, m) for m in
            ("collective_pct.replay", "exchange_roofline.replay")}
    assert read["collective_pct.replay"](ctx) == pytest.approx(
        100 * 0.266 / 5.995)
    # 283,600 x 3/4 rows of 272 B = 57.9 MB a chip, at 200 GB/s 0.289 ms
    share = read["exchange_roofline.replay"](ctx)
    assert share == pytest.approx(
        100 * (283_600 * 0.75 * 272 / 200e9) / 2.66e-3)
    assert 10 < share < 12
    # an exchange that moved the needed rows alone at the peak reads 100
    need_s = 283_600 * 0.75 * 272 / 200e9
    assert read["exchange_roofline.replay"](
        _ctx(collective_s=need_s * 100, busy_s=5.0)) == pytest.approx(100)


@pytest.mark.parametrize("hole", ["trace", "least", "collective"])
def test_readers_find_nothing_without_their_source(monkeypatch, hole):
    monkeypatch.setattr(exchange, "device_kind", lambda: "TPU v5 lite")
    ctx = _ctx(0.266, 5.995)
    if hole == "collective":
        # one chip, or a program without a collective: nothing to divide
        ctx["trace"]["collective_s_fullest"] = 0.0
        assert exchange.collective_pct(ctx) == 0.0
    else:
        ctx[hole] = None
    assert exchange.exchange_roofline(ctx) is None
    if hole == "trace":
        assert exchange.collective_pct(ctx) is None
