"""Fused sparse-FM kernel + on-device dedup (ISSUE 13, ROADMAP item 3).

Acceptance legs:

- trajectories are BYTE-identical across ``fused_kernel=off|jnp`` (and
  ``pallas`` via interpret mode — the same kernels Mosaic compiles on
  TPU, executed bit-exactly on CPU) at the step level AND through full
  learner runs at fs=1 and fs=4;
- the on-device dedup (ops/fused.dedup_tokens) reproduces the host
  ``np.unique`` + ``pad_slots_oob`` contract exactly, and a streamed
  ``device_dedup=1`` learner run is byte-identical to the host-dedup
  run;
- backend resolution fails typed where the backend cannot exist
  (pallas under a sharded table) and degrades to ``off`` on flat
  tables.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from difacto_tpu.learners import Learner
from difacto_tpu.losses import create
from difacto_tpu.ops import fused
from difacto_tpu.step import make_step_fns
from difacto_tpu.store.local import pad_slots_oob
from difacto_tpu.updaters.sgd_updater import (SGDUpdaterParam, init_state,
                                              make_fns, set_all_live)

from conftest import write_uniform_libsvm


def _table_bits(state_vvg) -> np.ndarray:
    """Bitwise table view: the scal section stores f32 BITS split into
    storage-dtype lanes, so float compares see spurious NaN != NaN —
    byte-identity is the uint view (updaters/sgd_updater.py pack_scal)."""
    v = np.asarray(jax.device_get(state_vvg))
    return v.view(np.uint16) if v.dtype != np.float32 \
        else v.view(np.uint32)


# ---------------------------------------------------------------- dedup

@pytest.mark.parametrize("seed", [0, 7])
def test_dedup_tokens_matches_host_unique(seed):
    rng = np.random.RandomState(seed)
    capacity = 512
    tok = rng.randint(1, 100, 300).astype(np.int32)
    uniq, inverse = np.unique(tok, return_inverse=True)
    u_cap = 128
    want_slots = pad_slots_oob(uniq.astype(np.int32), u_cap, capacity)
    slots, inv, n = jax.jit(
        lambda t: fused.dedup_tokens(t, u_cap, capacity))(jnp.asarray(tok))
    assert int(n) == len(uniq)
    np.testing.assert_array_equal(np.asarray(slots), want_slots)
    np.testing.assert_array_equal(np.asarray(inv), inverse)


def test_dedup_tokens_single_value():
    slots, inv, n = fused.dedup_tokens(
        jnp.full((16,), 5, jnp.int32), 8, 64)
    assert int(n) == 1
    assert np.asarray(slots).tolist() == [5] + list(range(65, 72))
    assert np.asarray(inv).tolist() == [0] * 16


# -------------------------------------------------------------- resolve

def test_resolve_backend_contract():
    assert fused.resolve_backend("off", V_dim=4) == "off"
    assert fused.resolve_backend("auto", V_dim=0) == "off"
    assert fused.resolve_backend("auto", V_dim=4) == "jnp"
    assert fused.resolve_backend("jnp", V_dim=4) == "jnp"
    with pytest.raises(ValueError, match="sharded"):
        fused.resolve_backend("pallas", mesh=object(), V_dim=4)
    with pytest.raises(ValueError, match="unknown fused_kernel"):
        fused.resolve_backend("mosaic", V_dim=4)
    # the knob validates at learner init too (Param enum metadata)
    param = SGDUpdaterParam(V_dim=2, fused_kernel="pallas")
    assert make_fns(param).backend == "pallas"


# ----------------------------------------------------- step trajectories

def _run_steps(fused_kernel, v_dtype, steps=5, vdim=8):
    from bench import make_batches
    param = SGDUpdaterParam(V_dim=vdim, V_threshold=0, lr=0.1, l1=1e-4,
                            l2=1e-4, V_dtype=v_dtype,
                            fused_kernel=fused_kernel)
    fns = make_fns(param)
    loss = create("fm", vdim)
    state = set_all_live(param, init_state(param, 512))
    _, train_step, _ = make_step_fns(fns, loss)
    step = jax.jit(train_step, donate_argnums=0)
    batches = make_batches(2, 32, 5, 128, 512, "zipf", seed=3)
    objs = []
    for i in range(steps):
        b, s = batches[i % 2]
        state, objv, auc = step(state, b, jnp.asarray(s))
        objs.append((float(objv), float(auc)))
    return objs, _table_bits(state.VVg)


@pytest.mark.parametrize("v_dtype", ["bfloat16", "float32"])
def test_trajectory_byte_identical_off_vs_jnp(v_dtype):
    o0, t0 = _run_steps("off", v_dtype)
    o1, t1 = _run_steps("jnp", v_dtype)
    assert o0 == o1                      # float equality, not allclose
    np.testing.assert_array_equal(t0, t1)


def test_trajectory_byte_identical_pallas_interpret():
    """The pallas kernels (interpret mode off-TPU — the same kernel
    bodies Mosaic compiles) reproduce the off-path trajectory
    bit-for-bit: gather, in-kernel FTRL/AdaGrad epilogue, DMA
    scatter-back, OOB pad handling."""
    o0, t0 = _run_steps("off", "bfloat16", steps=3)
    o2, t2 = _run_steps("pallas", "bfloat16", steps=3)
    assert o0 == o2
    np.testing.assert_array_equal(t0, t2)


def test_pallas_is_refused_typed_on_a_tpu_backend(monkeypatch):
    """Mosaic does not compile these kernels (ops/fused._MOSAIC_REFUSAL,
    taken on the chip): on a TPU backend the knob must raise at
    resolution, in the compiler's words — never crash mid-run, never
    reach interpret mode."""
    monkeypatch.setattr(fused.jax, "default_backend", lambda: "tpu")
    assert not fused.interpret_mode()
    with pytest.raises(fused.PallasRefused, match="Mosaic failed to "
                                                  "compile TPU kernel"):
        fused.resolve_backend("pallas", V_dim=8)
    assert fused.resolve_backend("auto", V_dim=8) == "jnp"


def test_pallas_gather_scatter_kernels_match_jnp():
    rng = np.random.RandomState(1)
    table = jnp.asarray(rng.randn(64, 16).astype(np.float32))
    slots = jnp.asarray(
        pad_slots_oob(np.array([1, 5, 9, 30, 63], np.int32), 12, 64))
    g_jnp = fused.gather_rows(table, slots, "jnp")
    g_pl = fused.gather_rows(table, slots, "pallas")
    np.testing.assert_array_equal(np.asarray(g_jnp), np.asarray(g_pl))
    rows = jnp.asarray(rng.randn(12, 16).astype(np.float32))
    s_jnp = fused.scatter_rows(table, slots, rows, "jnp")
    s_pl = fused.scatter_rows(table, slots, rows, "pallas")
    np.testing.assert_array_equal(np.asarray(s_jnp), np.asarray(s_pl))


# --------------------------------------------------------- learner runs

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


def test_learner_byte_equality_fs1(rcv1_path):
    s0, t0 = _learner_run(rcv1_path, fused_kernel="off")
    s1, t1 = _learner_run(rcv1_path, fused_kernel="jnp")
    assert s0 == s1
    np.testing.assert_array_equal(t0, t1)


def test_learner_byte_equality_fs4(rcv1_path):
    """fused_kernel=off|jnp stay byte-identical under the fs=4 sharded
    table (the jnp fused path partitions like the composed one and the
    state_constrainer keeps the donated layout)."""
    s0, t0 = _learner_run(rcv1_path, fused_kernel="off", mesh_fs=4)
    s1, t1 = _learner_run(rcv1_path, fused_kernel="jnp", mesh_fs=4)
    assert s0 == s1
    np.testing.assert_array_equal(t0, t1)


def test_pallas_knob_rejected_on_mesh(rcv1_path):
    ln = Learner.create("sgd")
    with pytest.raises(ValueError, match="sharded"):
        ln.init([("data_in", rcv1_path), ("V_dim", "2"),
                 ("hash_capacity", "4096"), ("mesh_fs", "4"),
                 ("fused_kernel", "pallas")])


# ----------------------------------------------------- device_dedup path

def test_device_dedup_trajectory_byte_identical(tmp_path):
    """Streamed hashed training with device_dedup=1 (raw token lanes,
    in-step sort/dedup) is byte-identical to the host-np.unique path —
    losses AND final table bits — across 3 epochs on panel-shaped
    data."""
    path = str(tmp_path / "u.libsvm")
    write_uniform_libsvm(path, rows=300, width=8, id_space=500)
    common = dict(device_cache_mb=0, producer_mode="thread",
                  max_num_epochs=3, num_jobs_per_epoch=2, batch_size=64)
    s0, t0 = _learner_run(path, **common)
    s1, t1 = _learner_run(path, device_dedup=1, **common)
    assert s0 == s1 and len(s0) == 3
    np.testing.assert_array_equal(t0, t1)


def test_device_dedup_prepare_produces_raw_payload(tmp_path):
    """prepare_hashed(device_dedup=True) ships the raw-panel payload
    past the count push, and falls back to host dedup while counts are
    being filled (epoch 0)."""
    from difacto_tpu.data.pack_stream import ShapeSchedule, prepare_hashed
    from difacto_tpu.data.rowblock import RowBlock
    rng = np.random.RandomState(0)
    width, rows = 6, 40
    blk = RowBlock(
        offset=np.arange(rows + 1, dtype=np.int64) * width,
        label=rng.randint(0, 2, rows).astype(np.float32),
        index=rng.randint(0, 10_000, rows * width).astype(np.uint64),
        value=None)
    shapes = ShapeSchedule()
    raw = prepare_hashed(shapes, 4096, blk, want_counts=False,
                         fill_counts=False, dim_min=8, job="t",
                         device_dedup=True)
    assert raw[0] == "panel_raw"
    kind, i32, f32, binary, b_cap, w, u_cap = raw
    assert w == width
    # trailing meta: [rows, distinct-count]; the u-cap covers the
    # distinct count + the TRASH lane pad cells may add
    assert i32[-2] == rows and i32[-1] <= u_cap - 1
    hosted = prepare_hashed(shapes, 4096, blk, want_counts=True,
                            fill_counts=True, dim_min=8, job="t",
                            device_dedup=True)
    assert hosted[0] in ("panel", "coo")   # count push -> host dedup


def test_device_dedup_skips_cached_regime(tmp_path):
    """With a replay cache active (the default), device_dedup never
    produces raw payloads — staged epochs replay from HBM and the raw
    path's target regime is pure streaming."""
    path = str(tmp_path / "u.libsvm")
    write_uniform_libsvm(path, rows=200, width=8, id_space=400)
    s0, t0 = _learner_run(path, max_num_epochs=2, device_dedup=1,
                          device_cache_mb=256)
    s1, t1 = _learner_run(path, max_num_epochs=2,
                          device_cache_mb=256)
    assert s0 == s1
    np.testing.assert_array_equal(t0, t1)
