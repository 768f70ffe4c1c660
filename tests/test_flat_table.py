"""ISSUE 35: the flat table (``V_dim = 0``, l1 logistic regression: w, z,
sqrt_g, cnt as flat float32 arrays) held to itself.

The benchmark's ``lr_l1_criteo.replay`` cell runs this layout on one chip;
what the cell cannot see is guarded here:

(a) ``store_gather_bytes_total{path=train}`` counts the bytes that the
    flat step's three scalar gathers and three scatters move, and the
    count is tied to the step program's own text and compiled form;
(b) the gauges ``model_nnz_w`` / ``model_penalty`` carry the epoch
    line's two numbers, into ``metrics_path`` too;
(c) one call of the pair-replay program equals two single replayed
    steps, bit for bit;
(d) the flat table under ``mesh_fs = 2`` (what the source's 50 key-range
    servers are; no cell has it) trains as on one device;
(e) ISSUE 36: compiled for a described v5e at the cell's shapes, the
    step's per-token gathers (``w[idx]``, ``p[idx]``) are row gathers of
    one slab of 128-lane rows that stays in fast memory, and no
    one-dimensional gather of the batch's cells is left.
"""

import json
import os
import re
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import write_uniform_libsvm

from difacto_tpu.learners import Learner
from difacto_tpu.updaters.sgd_updater import (SGDUpdaterParam, gather_bytes,
                                              scatter_bytes)

# an l1 that zeroes some and keeps some of the weights these tiny batches
# touch; l2 as the deployment's
ARGS = dict(V_dim=0, lr=0.1, l1=1.0, l2=0.02, num_jobs_per_epoch=1,
            batch_size=32, max_num_epochs=4, shuffle=0, report_interval=0,
            stop_rel_objv=0, hash_capacity=2048, producer_mode="thread",
            device_cache_mb=16)


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    return write_uniform_libsvm(
        str(tmp_path_factory.mktemp("flat") / "u.libsvm"), rows=128)


def _learner(data, **over):
    ln = Learner.create("sgd")
    args = dict(ARGS, data_in=data, **over)
    assert ln.init([(k, str(v)) for k, v in args.items()]) == []
    return ln


def _wait_pair_compile():
    for t in threading.enumerate():
        if t.name == "pair-exec-compile":
            t.join()


# ------------------------------------------------------- (a) the bytes
def test_flat_bytes_are_three_gathers_and_three_scatters():
    flat = SGDUpdaterParam(V_dim=0, hash_capacity=2048)
    assert gather_bytes(flat, 2048, 128, training=True) == 128 * 4 * 3
    assert gather_bytes(flat, 2048, 128) == 128 * 4      # predict: w alone
    assert scatter_bytes(flat, 2048, 128) == 128 * 4 * 3
    # a fused row moves whole, the same bytes each way on every path
    fused = SGDUpdaterParam(V_dim=4, hash_capacity=2048)
    row = gather_bytes(fused, 2048, 128)
    assert row == gather_bytes(fused, 2048, 128, training=True) \
        == scatter_bytes(fused, 2048, 128) and row % 128 == 0


def test_flat_byte_count_is_the_step_programs(data):
    """The unit of the counter against the program it counts. The flat
    train step's text gathers FOUR times from a table leaf (``w`` in
    ``get_rows``, then ``w`` again with ``sqrt_g`` and ``z`` in
    ``apply_grad``) over three distinct leaves, and scatters into three;
    the compiled program gathers three times: the second read of ``w``
    is merged away."""
    ln = _learner(data)
    cap, b, w, u = 2048, 32, 8, 128
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), ln.store.state)
    i32 = jax.ShapeDtypeStruct((b * w + u + 2,), jnp.int32)
    f32 = jax.ShapeDtypeStruct((b * w + 3 * b + u,), jnp.float32)
    low = ln._packed_panel_train.lower(state, i32, f32, b, w, u, False,
                                       False)
    text = low.as_text()
    pulls = re.findall(
        rf'"stablehlo\.gather"\((%arg\d+), [^)]*\).*?'
        rf'\(tensor<{cap}xf32>, tensor<{u}x1xi32>\) -> tensor<{u}xf32>',
        text)
    pushes = re.findall(r'"stablehlo\.scatter"\(%arg\d+, ', text)
    assert len(pulls) == 4 and len(set(pulls)) == 3 and len(pushes) == 3
    assert pulls[0] == pulls[1]          # w, twice
    hlo = low.compile().as_text()
    moved = re.findall(rf"= f32\[{u}(?:,1)?\]\S* gather\(.*"
                       r"indices_are_sorted=true", hlo)
    assert len(moved) == 3, moved
    p = ln.store.param
    assert gather_bytes(p, cap, u, training=True) == len(moved) * u * 4
    assert scatter_bytes(p, cap, u) == len(pushes) * u * 4


def test_flat_train_counter_counts_pull_and_push(data):
    ln = _learner(data, max_num_epochs=3)
    ln.add_epoch_end_callback(lambda e, t, v: _wait_pair_compile())
    ln.run()
    steps = 3 * (128 // 32)
    u_cap = ln._shapes.snapshot()["train.u"]
    # streamed, replayed singly and replayed in pairs alike: 6 float32
    # scalars a slot of the row cap (three gathered, three scattered)
    assert ln._paired_dispatches > 0
    assert ln.obs.value("store_gather_bytes_total", path="train") \
        == steps * u_cap * 4 * 6
    assert ln.obs.value("step_row_cap_total", job="train") == steps * u_cap


# ------------------------------------------------------ (b) the gauges
def test_model_gauges_say_what_the_epoch_line_prints(data, tmp_path,
                                                     caplog):
    path = str(tmp_path / "m.jsonl")
    ln = _learner(data, metrics_path=path)
    at_end = []
    ln.add_epoch_end_callback(lambda e, t, v: at_end.append(
        (t.nnz_w, t.penalty, ln.obs.value("model_nnz_w", job="train"),
         ln.obs.value("model_penalty", job="train"))))
    with caplog.at_level("INFO", logger="difacto_tpu"):
        ln.run()
    assert len(at_end) == 4
    for nnz, penalty, g_nnz, g_penalty in at_end:
        assert g_nnz == float(nnz) and g_penalty == float(penalty)
    # the line itself
    said = [r.getMessage() for r in caplog.records
            if "nnz(w) = " in r.getMessage()]
    last = re.search(r"nnz\(w\) = (\S+), penalty = (\S+)", said[-1])
    nnz, penalty = at_end[-1][:2]
    assert float(last.group(1)) == pytest.approx(nnz, rel=1e-5)
    assert float(last.group(2)) == pytest.approx(penalty, rel=1e-5)
    # l1 = 1 keeps some of the touched weights and zeroes the rest
    w = np.asarray(ln.store.state.w)
    assert 0 < nnz == np.count_nonzero(w[1:]) < np.count_nonzero(
        np.asarray(ln.store.state.sqrt_g))
    assert penalty == pytest.approx(
        np.sum(np.abs(w[1:]) + 0.5 * 0.02 * w[1:] ** 2), rel=1e-5)
    # and the flusher's last line
    with open(path) as f:
        gauges = json.loads(f.readlines()[-1])["metrics"]["gauges"]
    assert list(gauges["model_nnz_w"].values()) == [float(nnz)]
    assert list(gauges["model_penalty"].values()) == [float(penalty)]


# --------------------------------------- (c) the pair against two steps
_PAIR = """
import threading
import numpy as np
from difacto_tpu.learners import Learner

def run(pairs):
    ln = Learner.create("sgd")
    args = dict(%(args)r, data_in=%(data)r)
    assert ln.init([(k, str(v)) for k, v in args.items()]) == []
    if not pairs:       # no pair program: every replayed step runs alone
        ln._warm_pair_exec = lambda arrays, statics: None
    losses = []

    def on_end(epoch, train, _val):
        losses.append(float(train.loss))
        for t in threading.enumerate():
            if t.name == "pair-exec-compile":
                t.join()

    ln.add_epoch_end_callback(on_end)
    ln.run()
    s = ln.store.state
    return (losses, [np.asarray(x).tobytes() for x in (s.w, s.z, s.sqrt_g)],
            getattr(ln, "_paired_dispatches", 0),
            int(np.count_nonzero(np.asarray(s.w))))

paired, single = run(True), run(False)
assert paired[2] >= 4 and single[2] == 0, (paired[2], single[2])
assert paired[3] > 0
assert paired[0] == single[0], (paired[0], single[0])
assert paired[1] == single[1]
print("byte-equal")
"""


def test_flat_pair_program_equals_two_single_steps(data):
    """Four epochs whose replays run in pairs against four whose replays
    run one batch a dispatch: the losses and the bytes of w, z and sqrt_g.
    In a process of its own with the CPU held to SSE4.2 (with FMA on,
    XLA's CPU codegen may round two programs of the same arithmetic
    differently in last bits: tests/test_owned_run.py)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_cpu_max_isa=SSE4_2")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", _PAIR % {"args": ARGS, "data": data}],
        env=env, cwd=root, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("byte-equal")


# ------------------------------------------------- (d) under a mesh
def test_flat_table_under_mesh_fs2_trains_as_one_device(data):
    """The flat table feature-sharded by key range over two devices
    against one device, within the float32 tolerance of
    tests/test_fs_sharding.py: the losses an epoch and the three leaves
    the push writes."""
    if len(jax.devices()) < 2:
        pytest.skip("needs two (virtual) devices")

    def run(**over):
        ln = _learner(data, **over)
        losses = []
        ln.add_epoch_end_callback(
            lambda e, t, v: losses.append(float(t.loss)))
        ln.run()
        return ln, losses

    one, seen1 = run()
    two, seen2 = run(mesh_fs=2)
    assert one.mesh is None and two.store.fs_count == 2
    assert two.store.state.w.sharding.spec[0] == "fs"
    np.testing.assert_allclose(seen2, seen1, rtol=1e-5)
    for leaf in ("w", "z", "sqrt_g"):
        a = np.asarray(getattr(one.store.state, leaf))
        b = np.asarray(getattr(two.store.state, leaf))
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-7,
                                   err_msg=leaf)
    # the same weights are exactly 0 on both: l1's sparsity survives the
    # shards
    w1, w2 = np.asarray(one.store.state.w), np.asarray(two.store.state.w)
    assert np.array_equal(w1 == 0, w2 == 0)
    assert 0 < np.count_nonzero(w1) < np.count_nonzero(
        np.asarray(one.store.state.sqrt_g))


# ------------------------- (e) the per-token gathers, as the TPU compiles them
@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_flat_step_gathers_lane_rows_in_slabs(topo):
    """The flat train step at the cell's shapes (2^29 rows, 65,536 x 39,
    row cap 294,912; the compile helper of ``tests/perfbench``, which
    lays out the head-less 7,274,528 chunk cells). Shapes only: nothing
    runs, and what it reads is the compiler's text and count, not a
    device number. About a minute and a half."""
    from jax.sharding import SingleDeviceSharding
    from difacto_tpu.losses.fm import _LANE_SLAB
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "perfbench"))
    import test_perfbench_compile as C
    cfg = C._config("lr_l1_criteo")
    assert cfg["hash_capacity"] == 2 ** 29 and cfg["V_dim"] == 0
    compiled = C._compile_step(cfg, None,
                               SingleDeviceSharding(topo.devices[0]))
    text = compiled.as_text()
    cells = C.B * C.F                     # 2,555,904: the forward's
    # every gather of the program by its result's shape
    shapes = [tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"= f32\[([\d,]+)\]\S* gather\(",
                                     text)]
    assert shapes, "no gather in the program's text"
    flat = [s for s in shapes if len(s) == 1]
    # the three pulls of the row cap stay scalar gathers from the table;
    # no one-dimensional gather is as long as the batch's cells
    assert flat and max(flat) == (C.U,), flat
    assert not [s for s in flat if s[0] >= cells]
    # the two per-token gathers: one slab of whole 128-lane rows, out of
    # w's [2304, 128] and the padded p's [513, 128], kept in fast memory
    # (S(1)) from the gather to the select + lane sum
    assert sorted(s for s in shapes if len(s) == 2) \
        == [(_LANE_SLAB, 128)] * 2, shapes
    for rows in (C.U // 128, -(-(C.B + 1) // 128)):
        assert re.search(rf"f32\[{rows},128\]\S* parameter\(", text), rows
    kept = re.findall(rf"= f32\[{_LANE_SLAB},128\]\{{[^}}]*S\(1\)\}} "
                      r"fusion\(.*kind=kCustom", text)
    assert len(kept) == 2, kept
    # one slab live at a time: the parent's 0.28 GB of temporaries, not
    # the 3.7 GB of all cells' rows at once
    total, m = C._per_device_bytes(compiled)
    assert total < 17 * 2 ** 29 + 0.28e9 + 0.3e9, (total, m)
