"""Tiered table capacity (ISSUE 19): quantized slots, sketch-based
admission, host-RAM cold tier.

Acceptance legs:

- DEFAULTS ARE BYTE-IDENTICAL: ``slot_dtype=fp32`` + ``admit_min_count=0``
  + cold tier off reproduces the knob-free learner run bit-for-bit, at
  fs=1 AND fs=4 — the new subsystem costs nothing when off;
- the quantized row's dequant/requant epilogue threads from pull to
  push like any other row (tests/test_fused.py
  test_threaded_step_matches_composed_step, int8 cases);
- sketch admission is deterministic across the thread and process
  producer transports (same (seed, epoch, part) mix on both);
- a quantized (and tiered) checkpoint round-trips through the
  verified-manifest path and serves/predicts within tolerance of the
  fp32 model;
- the cold tier's promote/demote churn is byte-exact, and the armed
  ``store.demote`` / ``store.promote`` faults degrade without losing a
  row (chaos marker).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from difacto_tpu.capacity import AdmissionFilter, ColdTier, CountMinSketch
from difacto_tpu.capacity.sketch import make_admission
from difacto_tpu.learners import Learner
from difacto_tpu.ops import fused
from difacto_tpu.store.local import (K_FEACOUNT, K_GRADIENT, SlotStore)
from difacto_tpu.updaters.sgd_updater import SGDUpdaterParam
from difacto_tpu.utils import faultinject as fi

from conftest import write_uniform_libsvm


def _table_bits(state_vvg) -> np.ndarray:
    v = np.asarray(jax.device_get(state_vvg))
    if v.dtype == np.float32:
        return v.view(np.uint32)
    if v.dtype == np.int8:
        return v.view(np.uint8)
    return v.view(np.uint16)


def _mk_store(**kw) -> SlotStore:
    base = dict(hash_capacity=64, V_dim=4, V_threshold=0, lr=0.1,
                V_lr=0.1)
    base.update(kw)
    p, rest = SGDUpdaterParam.init_allow_unknown(
        [(k, str(v)) for k, v in base.items()])
    assert rest == []
    return SlotStore(p)


def _train_store(st: SlotStore, keys: np.ndarray, rounds: int = 3,
                 seed: int = 0) -> None:
    rng = np.random.default_rng(seed)
    for _ in range(rounds):
        k = np.sort(rng.choice(keys, size=min(8, len(keys)),
                               replace=False))
        st.push(k, K_FEACOUNT, np.ones(len(k), np.float32))
        st.pull(k)
        g = rng.standard_normal(len(k)).astype(np.float32) * 0.1
        gV = rng.standard_normal(
            (len(k), st.param.V_dim)).astype(np.float32) * 0.01
        st.push(k, K_GRADIENT, g, gV, np.ones(len(k), bool))


# ----------------------------------------------------------------- sketch

def test_count_min_never_undercounts():
    cms = CountMinSketch(width=1 << 10, depth=2, seed=3)
    rng = np.random.RandomState(0)
    tok = rng.randint(0, 500, 4000)
    cms.add(tok)
    true = np.bincount(tok, minlength=500)
    est = cms.estimate(np.arange(500))
    assert np.all(est >= true)


def test_count_min_deterministic_across_instances():
    tok = np.arange(100) % 13
    a = CountMinSketch(seed=9)
    b = CountMinSketch(seed=9)
    np.testing.assert_array_equal(a.add(tok), b.add(tok))
    np.testing.assert_array_equal(a.counts, b.counts)


def test_admission_filter_sentinel_and_threshold():
    f = AdmissionFilter(hash_capacity=100, min_count=3, seed=1)
    tok = np.array([7, 7, 7, 8], dtype=np.int32)
    out = f.filter(tok)
    # the whole batch is counted before the estimate readback, so all
    # three 7s see est=3 and admit; the lone 8 (est=1) remaps to the
    # OOB sentinel (=capacity)
    assert out.tolist() == [7, 7, 7, 100]
    # second pass: 8 reaches estimate 2 — still below min_count=3
    out2 = f.filter(tok)
    assert out2.tolist() == [7, 7, 7, 100]
    # third pass crosses the threshold for 8
    out3 = f.filter(tok)
    assert out3.tolist() == [7, 7, 7, 8]


def test_make_admission_off_and_mix():
    assert make_admission(64, 0, seed=1, epoch=0, part=0) is None
    a = make_admission(64, 2, seed=1, epoch=0, part=3)
    b = make_admission(64, 2, seed=1, epoch=0, part=3)
    c = make_admission(64, 2, seed=1, epoch=1, part=3)
    tok = (np.arange(50) % 7).astype(np.int32)
    np.testing.assert_array_equal(a.sketch.add(tok), b.sketch.add(tok))
    assert not np.array_equal(a.sketch._mult, c.sketch._mult)


# ------------------------------------------------------------- quantizer

@pytest.mark.parametrize("slot_dtype", ["int8", "fp8"])
def test_requant_idempotent(slot_dtype):
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(16, 8).astype(np.float32) * 0.03)
    codes, scale = fused.quant_half(x, slot_dtype)
    deq = fused.dequant_half(codes, scale, slot_dtype)
    codes2, scale2 = fused.quant_half(deq, slot_dtype)
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(codes2))
    np.testing.assert_array_equal(np.asarray(scale), np.asarray(scale2))


# ------------------------------------------------------------- cold tier

def test_tier_route_sorted_unique_with_pads():
    st = _mk_store(cold_tier_rows=32)
    tier = st.tier
    assert isinstance(tier, ColdTier)
    slots = np.array([1, 5, 40, 50, 64, 65], dtype=np.int64)  # 64+ = pads
    routed, order, perm = tier.route(slots)
    assert np.all(np.diff(routed) > 0)           # strictly sorted
    d = tier.D
    assert int((routed >= d).sum()) == 2         # the two pads stay OOB
    np.testing.assert_array_equal(routed[perm], routed[perm])
    # perm maps input position -> routed position of that same slot
    for p, s in enumerate(slots[:4]):
        assert routed[perm[p]] == tier._resident[s]


def test_tier_promote_demote_churn_byte_exact():
    # D = 32 device rows: every 16-key batch fits, but the 48 distinct
    # slots touched below force trained rows through demote + promote
    st = _mk_store(hash_capacity=256, slot_dtype="int8",
                   cold_tier_rows=224, seed=5)
    keys = np.arange(1, 400, 3, dtype=np.int64)
    _train_store(st, keys[:16], rounds=3, seed=1)
    w0, V0, _ = st.pull(np.sort(keys[:16]))
    # force churn: touch many other keys so the trained rows demote and
    # re-promote through the host tier repeatedly
    for i in range(4):
        st.pull(np.sort(keys[16 + 8 * i:24 + 8 * i]))
    w1, V1, _ = st.pull(np.sort(keys[:16]))
    np.testing.assert_array_equal(w0, w1)
    np.testing.assert_array_equal(V0, V1)


@pytest.mark.parametrize("slot_dtype", ["fp32", "int8"])
def test_tiered_checkpoint_round_trip(tmp_path, slot_dtype):
    keys = np.array([3, 11, 57, 999933, 12345, 777, 42, 5150, 90210,
                     1234567, 88, 4096], dtype=np.int64)
    st = _mk_store(slot_dtype=slot_dtype, cold_tier_rows=32, seed=7)
    _train_store(st, keys, rounds=6, seed=1)
    w0, V0, _ = st.pull(np.sort(keys))
    path = str(tmp_path / "m")
    st.save(path)
    st2 = _mk_store(slot_dtype=slot_dtype, cold_tier_rows=32, seed=7)
    st2.load(path)
    w1, V1, _ = st2.pull(np.sort(keys))
    # logical f32 arrays requantize through build_rows on load; with the
    # per-row scales round-tripping exactly this is byte-exact
    np.testing.assert_array_equal(w0, w1)
    np.testing.assert_array_equal(V0, V1)


def test_quantized_checkpoint_loads_untiered_and_stamps(tmp_path):
    """A tiered int8 save is a plain LOGICAL checkpoint: an untiered
    store of the full hash_capacity loads it and serves the same rows,
    and the stamps route a serving load to the same representation."""
    keys = np.arange(2, 40, 3, dtype=np.int64)
    st = _mk_store(slot_dtype="int8", cold_tier_rows=32, seed=7)
    _train_store(st, keys, rounds=4, seed=2)
    w0, V0, _ = st.pull(np.sort(keys))
    path = str(tmp_path / "m")
    st.save(path)

    from difacto_tpu.serve.model import model_meta, open_serving_store
    meta = model_meta(path)
    assert meta["slot_dtype"] == "int8"
    flat = _mk_store(slot_dtype="int8", cold_tier_rows=0, seed=7)
    flat.load(path)
    w1, V1, _ = flat.pull(np.sort(keys))
    np.testing.assert_array_equal(w0, w1)
    np.testing.assert_array_equal(V0, V1)

    store, meta2, _ = open_serving_store(path)
    # serving adopts the quantized representation but NEVER the tier
    assert store.param.slot_dtype == "int8"
    assert store.param.cold_tier_rows == 0 and store.tier is None
    w2, _, _ = store.pull(np.sort(keys))
    np.testing.assert_array_equal(w0, w2)


def test_occupancy_eviction_without_tier():
    st = _mk_store(hash_capacity=32, evict_occupancy=0.5, seed=3)
    keys = np.arange(1, 200, 7, dtype=np.int64)
    _train_store(st, keys, rounds=4, seed=3)
    n = st.maybe_evict()
    assert n > 0
    # occupancy dropped to <= 0.9 * threshold
    stn = st._state_np(st.state, keys=("w", "cnt", "v_live"))
    occ = (stn["w"] != 0) | (stn["cnt"] != 0) | np.asarray(
        stn["v_live"], bool)
    occ[0] = False
    assert occ.sum() <= 0.9 * 0.5 * 31 + 1
    # idempotent below threshold
    assert st.maybe_evict() == 0


def test_occupancy_eviction_with_tier_keeps_rows_addressable():
    st = _mk_store(hash_capacity=64, cold_tier_rows=32,
                   evict_occupancy=0.4, seed=3)
    keys = np.arange(1, 150, 5, dtype=np.int64)
    _train_store(st, keys, rounds=4, seed=4)
    w0, V0, _ = st.pull(np.sort(keys))
    n = st.maybe_evict()
    assert n > 0
    # under a tier, eviction demotes: every row still fully serves
    w1, V1, _ = st.pull(np.sort(keys))
    np.testing.assert_array_equal(w0, w1)
    np.testing.assert_array_equal(V0, V1)


def test_capacity_stats_multiplier():
    st = _mk_store(hash_capacity=256, slot_dtype="int8",
                   cold_tier_rows=128)
    s = st.capacity_stats()
    assert s["logical_rows"] == 256 and s["device_rows"] == 128
    assert s["capacity_multiplier"] >= 8.0
    base = _mk_store(hash_capacity=256).capacity_stats()
    assert base["capacity_multiplier"] == 1.0


def test_tier_requires_fused_layout_and_no_mesh():
    with pytest.raises(ValueError, match="V_dim"):
        _mk_store(V_dim=0, cold_tier_rows=16)
    with pytest.raises(ValueError, match="cold_tier_rows"):
        _mk_store(hash_capacity=64, cold_tier_rows=63)


# ------------------------------------------------------ learner parity

def _learner_run(data, **over):
    args = [("data_in", data), ("V_dim", "2"), ("V_threshold", "2"),
            ("lr", "0.1"), ("l1", "0.1"), ("l2", "0"),
            ("num_jobs_per_epoch", "1"), ("batch_size", "100"),
            ("max_num_epochs", "2"), ("shuffle", "0"),
            ("report_interval", "0"), ("stop_rel_objv", "0"),
            ("hash_capacity", "4096")]
    args += [(k, str(v)) for k, v in over.items()]
    ln = Learner.create("sgd")
    assert ln.init(args) == []
    seen = []
    ln.add_epoch_end_callback(lambda e, t, v: seen.append(t.loss))
    ln.run()
    return seen, _table_bits(ln.store.state.VVg)


def test_defaults_byte_identical_fs1(rcv1_path):
    """Explicitly passing every capacity knob at its default reproduces
    the knob-free run bit-for-bit: the subsystem is invisible when off."""
    s0, t0 = _learner_run(rcv1_path)
    s1, t1 = _learner_run(rcv1_path, slot_dtype="fp32",
                          admit_min_count=0, evict_occupancy=0,
                          cold_tier_rows=0)
    assert s0 == s1
    np.testing.assert_array_equal(t0, t1)


def test_defaults_byte_identical_fs4(rcv1_path):
    s0, t0 = _learner_run(rcv1_path, mesh_fs=4)
    s1, t1 = _learner_run(rcv1_path, mesh_fs=4, slot_dtype="fp32",
                          admit_min_count=0, evict_occupancy=0,
                          cold_tier_rows=0)
    assert s0 == s1
    np.testing.assert_array_equal(t0, t1)


def test_admission_thread_vs_process_deterministic(tmp_path):
    """The (seed, epoch, part) -> sketch mix is shared by both producer
    transports, so an admission-gated streamed run lands on the same
    admitted set — and the same table bits — thread or process."""
    path = str(tmp_path / "u.libsvm")
    write_uniform_libsvm(path, rows=300, width=8, id_space=500)
    common = dict(device_cache_mb=0, admit_min_count=2,
                  max_num_epochs=3, num_jobs_per_epoch=2, batch_size=64)
    s0, t0 = _learner_run(path, producer_mode="thread", **common)
    s1, t1 = _learner_run(path, producer_mode="process", **common)
    assert s0 == s1 and len(s0) == 3
    np.testing.assert_array_equal(t0, t1)


def test_admission_changes_the_admitted_set(tmp_path):
    path = str(tmp_path / "u.libsvm")
    write_uniform_libsvm(path, rows=200, width=8, id_space=400)
    _, t0 = _learner_run(path, device_cache_mb=0,
                         producer_mode="thread")
    _, t1 = _learner_run(path, device_cache_mb=0,
                         producer_mode="thread", admit_min_count=4)
    assert not np.array_equal(t0, t1)


def test_tiered_learner_run_matches_untiered(tmp_path):
    """A cold-tier learner run converges to the same model as the
    untiered run of the same data: residency is pure placement. The
    tier gates the device staging fast paths (stream-chunk, on-device
    dedup), so fp32 summation order shifts — close, not bit-equal."""
    path = str(tmp_path / "u.libsvm")
    write_uniform_libsvm(path, rows=200, width=8, id_space=300)
    common = dict(device_cache_mb=0, producer_mode="thread",
                  hash_capacity=1024, V_threshold=0)
    ln_args = [("data_in", path), ("V_dim", "2"), ("lr", "0.1"),
               ("l1", "0.1"), ("l2", "0"), ("num_jobs_per_epoch", "1"),
               ("batch_size", "100"), ("max_num_epochs", "2"),
               ("shuffle", "0"), ("report_interval", "0"),
               ("stop_rel_objv", "0")]

    def run(cold):
        ln = Learner.create("sgd")
        args = ln_args + [(k, str(v)) for k, v in common.items()]
        args += [("cold_tier_rows", str(cold))]
        assert ln.init(args) == []
        seen = []
        ln.add_epoch_end_callback(lambda e, t, v: seen.append(t.loss))
        ln.run()
        return seen, ln.store

    s0, st0 = run(0)
    s1, st1 = run(512)
    np.testing.assert_allclose(s0, s1, rtol=1e-5)
    keys = np.arange(0, 300, dtype=np.int64)
    w0, _, _ = st0.pull(keys)
    w1, _, _ = st1.pull(keys)
    # V is excluded: the tier draws its own virgin-init stream for the
    # tail, so per-slot V starts (and stays) on a different random walk
    np.testing.assert_allclose(w0, w1, rtol=1e-3, atol=1e-6)


# --------------------------------------------------------- pred parity

def test_quantized_checkpoint_pred_parity(rcv1_path, tmp_path):
    """task=pred from an int8 checkpoint tracks the fp32 golden
    predictions within quantization tolerance — the CLI round trip the
    serving path takes (model_meta slot_dtype stamp -> re-quantized
    weights-only load)."""
    from difacto_tpu.__main__ import main

    def train_pred(slot_dtype):
        model = str(tmp_path / f"m_{slot_dtype}")
        pred = str(tmp_path / f"p_{slot_dtype}")
        assert main([f"data_in={rcv1_path}", "lr=1", "l1=1", "l2=1",
                     "V_dim=2", "V_threshold=2", "batch_size=100",
                     "max_num_epochs=3", "shuffle=0",
                     "num_jobs_per_epoch=1", "report_interval=0",
                     f"slot_dtype={slot_dtype}",
                     f"model_out={model}"]) == 0
        assert main(["task=pred", f"model_in={model}", "V_dim=2",
                     f"slot_dtype={slot_dtype}",
                     f"data_val={rcv1_path}", "report_interval=0",
                     f"pred_out={pred}"]) == 0
        return np.array([float(l.split()[-1]) for l in
                         open(pred + "_part-0").read().splitlines()])

    golden = train_pred("fp32")
    quant = train_pred("int8")
    assert len(golden) == len(quant) == 100
    # same sign structure and close scores: quantization noise only
    assert np.mean(np.abs(golden - quant)) < 0.05
    assert np.corrcoef(golden, quant)[0, 1] > 0.98


# --------------------------------------------------------------- chaos

@pytest.mark.chaos
def test_chaos_demote_fault_keeps_victims_serving():
    """Armed ``store.demote:err@1``: every demotion batch is refused —
    victims stay resident and keep serving their exact values, new cold
    keys degrade to OOB zeros for the batch, nothing tears."""
    st = _mk_store(hash_capacity=256, slot_dtype="int8",
                   cold_tier_rows=224, seed=9)
    big = np.arange(1, 400, 3, dtype=np.int64)
    _train_store(st, big[:20], rounds=2, seed=4)
    wpre, Vpre, _ = st.pull(np.sort(big[:20]))
    res_pre = st.tier._resident.copy()
    fi.configure("store.demote:err@1")
    try:
        st.pull(np.sort(big[20:50]))
        assert fi.stats().get("store.demote", 0) > 0
    finally:
        fi.configure("")
    np.testing.assert_array_equal(res_pre, st.tier._resident)
    wpost, Vpost, _ = st.pull(np.sort(big[:20]))
    np.testing.assert_array_equal(wpre, wpost)
    np.testing.assert_array_equal(Vpre, Vpost)


@pytest.mark.chaos
def test_chaos_promote_fault_degrades_batch_only():
    """Armed ``store.promote:err@1``: the promote is refused before the
    scatter — the missing slots read zeros through the OOB lanes for
    this batch, and the store keeps serving its trained rows."""
    st = _mk_store(hash_capacity=256, slot_dtype="fp32",
                   cold_tier_rows=224, seed=9)
    big = np.arange(1, 400, 3, dtype=np.int64)
    _train_store(st, big[:10], rounds=2, seed=5)
    fi.configure("store.promote:err@1")
    try:
        w, V, _ = st.pull(np.sort(big[60:80]))
        assert fi.stats().get("store.promote", 0) > 0
    finally:
        fi.configure("")
    assert np.all(w == 0)
    w2, V2, _ = st.pull(np.sort(big[:10]))
    assert V2 is not None and np.any(V2 != 0)
