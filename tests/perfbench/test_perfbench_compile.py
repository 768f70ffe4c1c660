"""Rehearsals that cost no chip time, all in this one file (one worker
loads the TPU compiler): the flagship step compiled for a described
v5e:2x2 at the cells' real sizes, its ``memory_analysis()`` read against
the chip's 16 GB, and the sharded cell run at a tiny size on four
virtual devices."""

import json
import os

import numpy as np
import pytest

import perfbench_tiny as tiny

HBM = 16e9
# the cells' step: rows, width, and the row cap that ~279k distinct
# table rows a step are padded to (``ops/batch.row_cap``'s ladder of
# eighths: 278528, 294912, 327680)
B, F, U = 65536, 39, 294912


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _config(name):
    with open(os.path.join(tiny.ROOT, "perfbench", "configs",
                           name + ".json")) as f:
        return json.load(f)


def _compile_step(cfg, mesh, one_chip):
    """The train step of ``cfg`` lowered for described devices -> the
    compiled program. Shapes only: nothing runs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from difacto_tpu.losses import create
    from difacto_tpu.ops.batch import PanelBatch, panel_chunk_tokens_flat
    from difacto_tpu.step import make_step_fns
    from difacto_tpu.updaters.sgd_updater import (SGDUpdaterParam,
                                                  init_state, make_fns)
    param = SGDUpdaterParam(
        V_dim=cfg["V_dim"], V_dtype=cfg["V_dtype"],
        V_threshold=cfg["V_threshold"], lr=cfg["lr"], l1=cfg["l1"],
        hash_capacity=cfg["hash_capacity"])
    cap = cfg["hash_capacity"]
    state = jax.eval_shape(lambda: init_state(param, cap))
    if mesh is None:
        rep = row = one_chip
        shardings = None
    else:
        rep = NamedSharding(mesh, P())
        row = NamedSharding(mesh, P("fs"))
        shardings = jax.tree_util.tree_map(
            lambda x: row if x.ndim and x.shape[0] == cap else rep, state)
    fns = make_fns(param, mesh=mesh)
    _, train, _ = make_step_fns(fns, create("fm", cfg["V_dim"]),
                                state_shardings=shardings)

    def sds(shape, dtype, sh):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    state_s = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype,
                      row if x.ndim and x.shape[0] == cap else rep), state)
    ci, cl, _ = jax.eval_shape(
        lambda f: panel_chunk_tokens_flat(f, None, U, B, F),
        jax.ShapeDtypeStruct((B * F,), jnp.int32))
    f32, i32 = jnp.float32, jnp.int32
    pb = PanelBatch(
        idx=sds((B, F), i32, rep), vals=None, labels=sds((B,), f32, rep),
        rweight=sds((B,), f32, rep), row_mask=sds((B,), f32, rep),
        num_rows=sds((), i32, rep), num_uniq=sds((), i32, rep),
        chunk_idx=sds(ci.shape, ci.dtype, rep),
        chunk_lane=sds(cl.shape, cl.dtype, rep), chunk_vals=None)
    slots = sds((U,), i32, rep)
    return jax.jit(train, donate_argnums=0).lower(state_s, pb,
                                                  slots).compile()


def _per_device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes), m


@pytest.mark.parametrize("name", ["fm_v64_criteo", "fm_v16_kaggle"])
def test_one_chip_step_fits_beside_its_batch_cache(topo, name):
    from jax.sharding import SingleDeviceSharding
    cfg = _config(name)
    assert cfg["hash_capacity"] == 2 ** 23
    total, m = _per_device_bytes(_compile_step(
        cfg, None, SingleDeviceSharding(topo.devices[0])))
    table = m.argument_size_in_bytes
    assert table >= 2 ** 23 * 512            # 4.29 GB: over the 25% floor
    assert table >= 0.25 * HBM
    # the donated table is updated in place, and the step leaves room for
    # the 4 GB batch cache of the replay traffic
    assert m.alias_size_in_bytes >= 2 ** 23 * 512
    assert total + 4096 * 2 ** 20 < HBM, (total, m)


def test_sharded_step_fits_a_quarter_a_chip(topo):
    from jax.sharding import Mesh
    cfg = _config("fm_v64_criteo_fs4")
    assert cfg["hash_capacity"] == 2 ** 25 and cfg["mesh_fs"] == 4
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("dp", "fs"))
    compiled = _compile_step(cfg, mesh, None)
    total, m = _per_device_bytes(compiled)
    shard = 2 ** 25 * 512 // 4
    assert m.alias_size_in_bytes >= shard    # each shard updated in place
    assert shard >= 0.25 * HBM
    assert total + 4096 * 2 ** 20 < HBM, (total, m)
    text = compiled.as_text()
    # the exchange of rows between the shards is in the program, and the
    # table itself is never gathered whole
    assert "all-reduce" in text or "all-gather" in text \
        or "collective-permute" in text or "all-to-all" in text
    assert "bf16[33554432,256]" not in text.replace(" ", "")


def test_sharded_cell_on_four_virtual_devices(tmp_path):
    """``fm_v64_criteo_fs4`` at a tiny size through the harness: the mesh
    path streams epoch 0 step by step, stages, replays, and its first
    steps agree with the reference."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    root = tiny.make_root(str(tmp_path), config="fm_v64_criteo_fs4",
                          capacity=4096, mesh_fs=4,
                          limits=tiny.FIRST_LIMITS)
    b = tiny.bench()
    b["configs"].append({"name": "fm_v64_criteo_fs4",
                         "file": "perfbench/configs/"
                                 "fm_v64_criteo_fs4.json"})
    b["workloads"].append({"name": "fm_v64_criteo_fs4.replay",
                           "config": "fm_v64_criteo_fs4",
                           "traffic": "replay", "chips": 4})
    for m in b["end_to_end"]:
        if m["name"] == "replay_ex_per_s":
            m["workloads"].append("fm_v64_criteo_fs4.replay")
    with open(os.path.join(root, "perfbench", "limits",
                           "fm_v64_criteo_fs4.replay.json"), "w") as f:
        json.dump(tiny.FIRST_LIMITS, f)
    from perfbench import run as R
    lines = {}
    res = R.run_cell(b, root, "fm_v64_criteo_fs4.replay", 5, 0.2, False,
                     require_tpu=False,
                     out=lambda k, v: lines.__setitem__(k, v))
    assert res["correct"] is True, res["checked"]
    win = json.loads(lines["window"])
    assert win["device_cache"]["3"]["complete"] and win["epochs"] >= 1
