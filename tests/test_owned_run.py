"""ISSUE 33: under ``mesh_fs`` a shard's table legs run over the rows it
owns.

``ops/fused.{gather,scatter}_rows`` with a mesh and a counted ``own_cap``
slice each fs shard's run of the sorted unique slots and gather / scatter
that run alone; the result must equal plain indexing bit for bit, for
every place the run can sit (balanced, one shard owning everything, an
empty shard, the last shard clamped so the previous shard's rows fall
inside its slice, pads only) and every row format. ``own_cap`` is counted
on the host (``SGDLearner._owned_cap``), rides the cached ``devbatch``
entry, and is never below the fullest shard's rows. The scatter declares
``indices_are_sorted`` only where the sweep it buys is the cheaper form
(``scatter_sweeps``).
"""

import os
import subprocess
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import write_uniform_libsvm
from jax.sharding import NamedSharding, PartitionSpec as P

from difacto_tpu.learners import Learner
from difacto_tpu.learners.sgd import K_TRAINING
from difacto_tpu.ops import fused
from difacto_tpu.ops.batch import row_cap
from difacto_tpu.parallel import fs_shard_bounds, make_mesh
from difacto_tpu.store.local import pad_slots_oob

C, U = 4096, 64          # table rows, row cap
ROWS = {"bf16x256": (jnp.bfloat16, 256), "f32x128": (jnp.float32, 128),
        "int8x256": (jnp.int8, 256)}


def _mesh(dp, fs):
    if dp * fs > len(jax.devices()):
        pytest.skip("needs four (virtual) devices")
    return make_mesh(dp=dp, fs=fs)


def _bits(x):
    """The unsigned-integer view of an array, on the host."""
    x = jnp.asarray(x)
    return np.asarray(jax.lax.bitcast_convert_type(
        x, {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]))


def _draw(rng, shape, dtype):
    if dtype == jnp.int8:
        return jnp.asarray(rng.integers(-128, 128, shape, np.int8))
    return jnp.asarray(rng.standard_normal(shape, np.float32)).astype(dtype)


def _slot_case(name, rng, fs):
    """The batch's real (sorted unique) slots for a named placement."""
    per = C // fs
    pick = lambda lo, hi, n: rng.choice(hi - lo, n, replace=False) + lo
    if name == "balanced":
        s = pick(1, C, 50)
    elif name == "one_shard":          # own_cap = U: the plain program
        s = pick(per, 2 * per, 60)
    elif name == "empty_shard":
        s = np.concatenate([pick(1, per, 20), pick(C - per, C, 20)])
    elif name == "clamped_last":
        # the last shard's run starts so late that the clamp pulls the
        # previous shard's rows into its slice (negative local indices)
        s = np.concatenate([pick(1, C - per, 40), pick(C - per, C, 24)])
    elif name == "full_cap":           # no pads at all
        s = pick(1, C, U)
    else:
        assert name == "pads_only"
        s = np.zeros(0, np.int64)
    return np.sort(s)


def _fullest(slots, fs):
    return int(np.bincount(slots // (C // fs), minlength=fs).max()) \
        if len(slots) else 0


@pytest.mark.parametrize("case", ["balanced", "one_shard", "empty_shard",
                                  "clamped_last", "full_cap", "pads_only"])
@pytest.mark.parametrize("rows", list(ROWS))
@pytest.mark.parametrize("dp, fs", [(1, 4), (2, 2)])
def test_owned_run_equals_plain_indexing(dp, fs, rows, case):
    mesh = _mesh(dp, fs)
    dtype, width = ROWS[rows]
    rng = np.random.default_rng(zlib.crc32(f"{fs}{rows}{case}".encode()))
    real = _slot_case(case, rng, fs)
    own_cap = min(U, -(-max(_fullest(real, fs), 1) // 8) * 8)
    assert (own_cap == U) is (case == "one_shard")
    table = _draw(rng, (C, width), dtype)
    new = _draw(rng, (U, width), dtype)
    slots = pad_slots_oob(real, U, C)
    rep = NamedSharding(mesh, P())
    t_dev = jax.device_put(table, NamedSharding(mesh, P("fs", None)))
    s_dev, n_dev = jax.device_put(slots, rep), jax.device_put(new, rep)

    got = jax.jit(lambda t, s: fused.gather_rows(t, s, mesh, own_cap))(
        t_dev, s_dev)
    want = np.zeros((U, width), _bits(table).dtype)
    want[:len(real)] = _bits(table)[real]
    np.testing.assert_array_equal(_bits(got), want)

    out = jax.jit(
        lambda t, s, r: fused.scatter_rows(t, s, r, mesh, own_cap),
        donate_argnums=0)(t_dev, s_dev, n_dev)
    assert out.sharding.spec == P("fs", None)
    want = _bits(table).copy()
    want[real] = _bits(new)[:len(real)]
    np.testing.assert_array_equal(_bits(out), want)


def test_owned_gather_keeps_bits_a_float_sum_would_round():
    """The sum over fs runs on the rows' bits: -0.0 and denormal
    patterns (what the low half of an f32 scalar looks like in a bf16
    lane) come back as one device reads them."""
    mesh = _mesh(1, 4)
    rng = np.random.default_rng(5)
    bits = rng.integers(0, 2 ** 16, (C, 8)).astype(np.uint16)
    bits[:, 0], bits[:, 1] = 0x8000, 0x8070     # -0.0, a denormal
    bits[(bits & 0x7f80) == 0x7f80] = 0x3f80    # the CPU's own gather
    # canonicalises NaNs on one device too: not what is under test
    table = jax.lax.bitcast_convert_type(jnp.asarray(bits), jnp.bfloat16)
    real = _slot_case("balanced", rng, 4)
    slots = pad_slots_oob(real, U, C)
    got = jax.jit(lambda t, s: fused.gather_rows(t, s, mesh, 24))(
        jax.device_put(table, NamedSharding(mesh, P("fs", None))),
        jax.device_put(slots, NamedSharding(mesh, P())))
    np.testing.assert_array_equal(_bits(got)[:len(real)], bits[real])


def test_own_cap_at_the_row_cap_is_the_plain_program():
    mesh = _mesh(1, 4)
    t = jax.ShapeDtypeStruct((C, 128), jnp.float32)
    s = jax.ShapeDtypeStruct((U,), jnp.int32)
    r = jax.ShapeDtypeStruct((U, 128), jnp.float32)
    # whether the lowered program holds a manual (shard_map) region
    manual = lambda f, *a: "manual_computation" in jax.jit(f).lower(
        *a).as_text()
    for own in (None, U):
        assert not manual(
            lambda t, s: fused.gather_rows(t, s, mesh, own), t, s)
        assert not manual(
            lambda t, s, r: fused.scatter_rows(t, s, r, mesh, own), t, s, r)
    assert manual(lambda t, s: fused.gather_rows(t, s, mesh, U // 2), t, s)
    assert manual(
        lambda t, s, r: fused.scatter_rows(t, s, r, mesh, U // 2), t, s, r)
    # one device, or a flat array under a mesh: never
    flat = jax.ShapeDtypeStruct((C,), jnp.float32)
    assert not manual(lambda t, s: fused.gather_rows(t, s, mesh, 8), flat, s)
    assert not manual(lambda t, s: fused.gather_rows(t, s, None, 8), t, s)


# the probe's shapes (ops/fused.py's comment): ms a call sorted / unsorted
@pytest.mark.parametrize("log_rows, indices, sweeps", [
    (23, 294912, True),     # 17.5 / 23.4: the one-chip cells (28.4)
    (23, 73728, False),     # 16.4 / 5.9: a shard's owned run (113.8)
    (23, 8192, False),      # 16.1 / 0.70
    (23, 64, False),        # 14.8 / 0.21
    (21, 294912, True),     # 5.5 / 7.8
    (22, 294912, True),     # 9.5 / 23.4
    (24, 294912, False),    # 33.5 / 23.4
    (21, 64, False), (24, 64, False), (21, 8192, False),
    (22, 73728, False), (21, 73728, True),
])
def test_sorted_flag_follows_the_shapes(log_rows, indices, sweeps):
    assert fused.scatter_sweeps(2 ** log_rows, indices) is sweeps
    table = jax.ShapeDtypeStruct((2 ** log_rows, 256), jnp.bfloat16)
    slots = jax.ShapeDtypeStruct((indices,), jnp.int32)
    new = jax.ShapeDtypeStruct((indices, 256), jnp.bfloat16)
    text = jax.jit(fused.scatter_rows).lower(table, slots, new).as_text()
    assert ("indices_are_sorted = true" in text) is sweeps
    assert "unique_indices = true" in text


def test_flat_tables_and_the_gather_keep_their_flags():
    slots = jax.ShapeDtypeStruct((64,), jnp.int32)
    flat = jax.ShapeDtypeStruct((2 ** 23,), jnp.float32)
    text = jax.jit(fused.scatter_rows).lower(
        flat, slots, jax.ShapeDtypeStruct((64,), jnp.float32)).as_text()
    assert "indices_are_sorted = true" in text
    table = jax.ShapeDtypeStruct((2 ** 23, 256), jnp.bfloat16)
    text = jax.jit(fused.gather_rows).lower(table, slots).as_text()
    assert "indices_are_sorted = true" in text
    # under a mesh the rule reads one shard's rows, not the table's
    mesh = _mesh(1, 4)
    big = jax.ShapeDtypeStruct((2 ** 25, 256), jnp.bfloat16)
    slots = jax.ShapeDtypeStruct((294912,), jnp.int32)
    new = jax.ShapeDtypeStruct((294912, 256), jnp.bfloat16)
    text = jax.jit(lambda t, s, r: fused.scatter_rows(t, s, r, mesh)
                   ).lower(big, slots, new).as_text()
    assert "indices_are_sorted = true" in text


# ------------------------------------------------------- the host count
ROWS_N, BATCH, EPOCHS = 1024, 256, 3
ARGS = dict(V_dim=4, V_threshold=0, lr=0.1, l1=1e-4, l2=0,
            num_jobs_per_epoch=1, batch_size=BATCH, max_num_epochs=EPOCHS,
            shuffle=0, report_interval=0, stop_rel_objv=0,
            hash_capacity=4096, producer_mode="thread", device_cache_mb=16)


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    return write_uniform_libsvm(
        str(tmp_path_factory.mktemp("owned") / "u.libsvm"), rows=ROWS_N,
        width=16, id_space=3000)


def _learner(data, **over):
    if over.get("mesh_fs", 1) * over.get("mesh_dp", 1) > len(jax.devices()):
        pytest.skip("needs four (virtual) devices")
    ln = Learner.create("sgd")
    args = dict(ARGS, data_in=data, **over)
    assert ln.init([(k, str(v)) for k, v in args.items()]) == []
    return ln


def test_own_cap_is_counted_sticky_and_never_short(data):
    ln = _learner(data, mesh_fs=4)
    cap = ln.store.state.capacity
    bounds = fs_shard_bounds(cap, 4)
    rng = np.random.default_rng(3)
    seen = 0
    for n, skew in [(40, None), (300, None), (300, 2), (900, None),
                    (900, 3), (120, 0), (2500, None)]:
        lo, hi = bounds[skew] if skew is not None else (1, cap)
        n = min(n, hi - lo)
        slots = np.sort(rng.choice(hi - lo, n, replace=False) + lo)
        u_cap = row_cap(n)
        rows, own = ln._owned_cap("train", slots, u_cap)
        assert rows == max(np.sum((slots >= a) & (slots < b))
                           for a, b in bounds)
        assert rows <= own <= u_cap
        # sticky on the row cap's ladder: the largest count so far, on
        # its rung, unless this batch's own row cap is below it
        seen = max(seen, row_cap(rows))
        assert own == min(seen, u_cap)
        if skew is not None:
            assert own == u_cap        # one shard owns the batch
    assert ln._shapes.snapshot()["train.own"] == seen
    # no feature sharding, no run
    assert _learner(data)._owned_cap("train", slots, u_cap) is None
    assert _learner(data, mesh_dp=2)._owned_cap("train", slots, u_cap) \
        is None


def test_a_cached_devbatch_replays_with_the_own_cap_it_was_staged_with(data):
    ln = _learner(data, mesh_fs=4)
    calls = []
    steps_for = ln._owned_steps

    def spy(owned, u_cap):
        calls.append(owned[1])
        return steps_for(owned, u_cap)
    ln._owned_steps = spy
    ln.run()
    steps = ROWS_N // BATCH
    cache = ln._dev_caches[K_TRAINING]
    staged = [pl for part in cache.entries.values() for pl in part]
    assert len(staged) == steps and len(calls) == EPOCHS * steps
    assert all(pl[0] == "devbatch" and pl[-1] > 0 for pl in staged)
    owned = [pl[5] for pl in staged]
    # epoch 0 ran what it staged; every replayed epoch runs that again,
    # whatever the sticky cap has grown to since
    assert calls == [cap for _, cap in owned] * EPOCHS
    assert all(rows <= cap < pl[2].shape[0]
               for (rows, cap), pl in zip(owned, staged))
    v = lambda name: ln.obs.value(name, job="train")
    assert v("store_owned_rows_total") == EPOCHS * sum(r for r, _ in owned)
    assert v("store_owned_cap_total") == EPOCHS * sum(c for _, c in owned)
    # the run engaged: about a quarter of the row cap, mostly full
    assert 0.2 < v("store_owned_cap_total") / v("step_row_cap_total") < 0.4
    assert v("store_owned_rows_total") / v("store_owned_cap_total") > 0.7
    # one pair of programs a rung of the sticky cap, built once
    assert set(ln._owned_step_fns) == {cap for _, cap in owned}


def test_without_feature_shards_nothing_is_counted(data):
    ln = _learner(data, max_num_epochs=1)
    ln.run()
    assert ln.obs.value("store_owned_cap_total", job="train") == 0
    assert ln.obs.value("step_row_cap_total", job="train") > 0


_TRAJECTORY = r"""
import sys
import jax, numpy as np
from difacto_tpu.learners import Learner
from difacto_tpu.learners.sgd import SGDLearner

def run(plain=False, **over):
    args = dict(%(args)r, data_in=%(data)r, **over)
    ln = Learner.create("sgd")
    assert ln.init([(k, str(v)) for k, v in args.items()]) == []
    if plain:       # the program GSPMD partitions: the parent's
        ln._owned_cap = lambda job, slots, u_cap: None
    losses = []
    ln.add_epoch_end_callback(lambda e, t, v: losses.append(float(t.loss)))
    ln.run()
    table = b"".join(np.asarray(x).tobytes()
                     for x in jax.tree_util.tree_leaves(ln.store.state))
    return losses, table, ln.obs.value("store_owned_cap_total", job="train")

for mesh in (dict(mesh_fs=4), dict(mesh_dp=2, mesh_fs=2)):
    owned, plain = run(**mesh), run(plain=True, **mesh)
    assert owned[2] > 0 and plain[2] == 0
    assert owned[0] == plain[0], (owned[0], plain[0])
    assert owned[1] == plain[1]
print("byte-equal")
"""


def test_trajectory_is_byte_equal_to_the_partitioned_programs(data):
    """A float32 trajectory over the owned run equals, to the byte, the
    one over the plain partitioned program (the parent's), at fs=4 and
    at (dp=2, fs=2). In a process of its own with the CPU held to SSE4.2:
    with FMA on, XLA's CPU codegen contracts the epilogue's multiply-adds
    differently around a select than around a bitcast, and two programs
    of the same arithmetic differ in last bits."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4 "
                         "--xla_cpu_max_isa=SSE4_2")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", _TRAJECTORY % {"args": ARGS, "data": data}],
        env=env, cwd=root, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("byte-equal")
