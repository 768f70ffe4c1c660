"""The fused table path + on-device dedup (ROADMAP item 3).

Acceptance legs:

- threading the gathered rows from the step's pull to its push equals
  re-gathering them: the train step (step.py) against a step composed
  in the test from the store's eager ``get_rows`` + ``apply_grad``,
  byte for byte, over both row dtypes, plain and int8-quantized rows,
  one device and an fs=4 sharded table;
- the on-device dedup (ops/fused.dedup_tokens) reproduces the host
  ``np.unique`` + ``pad_slots_oob`` contract exactly, and a streamed
  ``device_dedup=1`` learner run is byte-identical to the host-dedup
  run;
- a conf that still names the removed ``fused_kernel`` key is reported
  as unknown, not swallowed.
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


# ----------------------------------------------------- step trajectories

def make_batches(n, B, nnz_per_row, uniq_space, capacity, seed=0):
    """Host-side localized PANEL batches (fixed-width [B, F] index matrix,
    the criteo layout) with zipf-skewed features, chunked for the FM
    backward, + sorted-unique slot vectors padded with ascending
    out-of-bounds indices (the table kernels' contract)."""
    from difacto_tpu.data.rowblock import RowBlock
    from difacto_tpu.ops.batch import bucket, pad_panel, panel_chunk_tokens

    rng = np.random.RandomState(seed)
    raw = []
    u_cap = 8
    for _ in range(n):
        idx = ((rng.zipf(1.25, B * nnz_per_row) - 1)
               % uniq_space).astype(np.int64)
        uniq, inverse = np.unique(idx, return_inverse=True)
        raw.append((uniq, inverse))
        u_cap = max(u_cap, bucket(len(uniq)))
    chunker = jax.jit(panel_chunk_tokens, static_argnums=(1,))
    out = []
    for uniq, inverse in raw:
        blk = RowBlock(
            offset=np.arange(B + 1, dtype=np.int64) * nnz_per_row,
            label=rng.choice([0.0, 1.0], B).astype(np.float32),
            index=inverse.astype(np.uint32),
            value=None)
        batch = chunker(pad_panel(blk, num_uniq=len(uniq), batch_cap=B,
                                  width=nnz_per_row), u_cap)
        slots = np.sort(rng.permutation(capacity - 1)[:len(uniq)] + 1)
        out.append((batch, pad_slots_oob(slots.astype(np.int32), u_cap,
                                         capacity)))
    return out


def _composed_step(fns, loss, constrain):
    """The reference: the same step built from the store's eager pull
    and push, which gather the rows once each, jitted whole."""
    from difacto_tpu.losses.metrics import auc_times_n_binned_jnp

    def step(state, batch, slots):
        params = fns.get_rows(state, slots)
        vmask = params.v_mask
        pred, xv = loss.predict_xv(params, batch)
        objv = loss.evaluate(pred, batch)
        auc = auc_times_n_binned_jnp(batch.labels, pred, batch.row_mask)
        gw, gV = loss.calc_grad(params, batch, pred, xv)
        state = fns.apply_grad(state, slots, gw, gV, vmask)
        return constrain(state), objv, auc
    return step


@pytest.mark.parametrize("fs", [1, 4], ids=["one_device", "mesh_fs4"])
@pytest.mark.parametrize("slot_dtype", ["fp32", "int8"])
@pytest.mark.parametrize("v_dtype", ["bfloat16", "float32"])
def test_threaded_step_matches_composed_step(v_dtype, slot_dtype, fs):
    from difacto_tpu.parallel import (make_mesh, sharding_tree,
                                      state_sharding)
    from difacto_tpu.step import state_constrainer
    if len(jax.devices()) < fs:
        pytest.skip("needs four (virtual) devices")
    vdim, cap, steps = 8, 512, 4
    param = SGDUpdaterParam(V_dim=vdim, V_threshold=0, lr=0.1, l1=1e-4,
                            l2=1e-4, V_dtype=v_dtype,
                            slot_dtype=slot_dtype)
    fns = make_fns(param)
    assert fns.fused
    loss = create("fm", vdim)
    batches = make_batches(2, 32, 5, 128, cap, seed=3)

    def run(build):
        state = set_all_live(param, init_state(param, cap))
        shardings = None
        if fs > 1:
            shardings = sharding_tree(
                state, state_sharding(make_mesh(dp=1, fs=fs)))
            state = jax.device_put(state, shardings)
        step = jax.jit(build(shardings), donate_argnums=0)
        objs = []
        for i in range(steps):
            b, s = batches[i % 2]
            state, objv, auc = step(state, b, jnp.asarray(s))
            objs.append((float(objv), float(auc)))
        return objs, _table_bits(state.VVg)

    o0, t0 = run(lambda sh: _composed_step(fns, loss,
                                           state_constrainer(sh)))
    o1, t1 = run(lambda sh: make_step_fns(fns, loss,
                                          state_shardings=sh)[1])
    assert o0 == o1                      # float equality, not allclose
    np.testing.assert_array_equal(t0, t1)
    # the steps trained: the table moved off its initial bits
    assert (t1 != _table_bits(set_all_live(
        param, init_state(param, cap)).VVg)).any()


# ---------------------------------------------------------- removed knob

def test_fused_kernel_key_is_reported_unknown(rcv1_path, tmp_path, caplog):
    """The switch is gone: a conf or command line that still sets it is
    told so, the way any unknown key is — a leftover of init, and
    main()'s "unknown config key" warning."""
    from difacto_tpu.__main__ import main
    ln = Learner.create("sgd")
    left = ln.init([("data_in", rcv1_path), ("V_dim", "2"),
                    ("hash_capacity", "4096"), ("fused_kernel", "jnp")])
    assert left == [("fused_kernel", "jnp")]
    conf = tmp_path / "old.conf"
    conf.write_text(f"data_in = {rcv1_path}\nV_dim = 2\n"
                    "hash_capacity = 4096\nfused_kernel = off\n")
    for argv in ([str(conf)], [str(conf), "fused_kernel=pallas"]):
        caplog.clear()
        with caplog.at_level("WARNING", logger="difacto_tpu"):
            assert main(argv + ["max_num_epochs=1",
                                "report_interval=0"]) == 0
        said = [r.getMessage() for r in caplog.records
                if "unknown config key" in r.getMessage()]
        assert said and all("fused_kernel" in m for m in said)


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
