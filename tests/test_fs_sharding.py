"""Mesh-sharded parameter table (ISSUE 12): key-range fs-sharding of the
slot store for train AND serve, on the 8-device virtual CPU mesh.

Covers the tentpole's acceptance legs:

- fs=1 degenerate-mesh trajectories are BYTE-identical to the unsharded
  path (the sharded program lowering must be free at fs=1);
- an fs>1 table trains end-to-end and round-trips through per-key-range
  shard checkpoints (one npz + manifest per shard, array-free stub as
  the generation commit marker), including the corrupt-one-shard
  walk-back;
- task=serve loads and queries an fs-sharded store with scores
  byte-identical to the single-device path, whatever layout the
  checkpoint was saved in;
- make_mesh's multi-host host-complete fs constraint fails typed;
- the capacity-scaling report (parallel/capacity.py /
  __graft_entry__.dryrun_multichip) emits per-fs legs with constant
  per-device bytes.
"""

import os

import numpy as np
import pytest

from difacto_tpu.learners import Learner
from difacto_tpu.parallel import (fs_shard_bounds, make_mesh,
                                  validate_fs_capacity)
from difacto_tpu.store.local import (CheckpointCorrupt, SlotStore,
                                     fs_shard_path)
from difacto_tpu.updaters.sgd_updater import (SGDUpdaterParam, col_V,
                                              scal_cols, state_bytes)


def _run(rcv1_path, **over):
    args = [("data_in", rcv1_path), ("V_dim", "2"), ("V_threshold", "2"),
            ("lr", "0.1"), ("l1", "0.1"), ("l2", "0"),
            ("num_jobs_per_epoch", "1"), ("batch_size", "100"),
            ("max_num_epochs", "3"), ("shuffle", "0"),
            ("report_interval", "0"), ("stop_rel_objv", "0"),
            ("hash_capacity", "4096")]
    args += [(k, str(v)) for k, v in over.items()]
    learner = Learner.create("sgd")
    assert learner.init(args) == []
    seen = []
    learner.add_epoch_end_callback(lambda e, t, v: seen.append(t.loss))
    learner.run()
    return learner, seen


def _state_cols(store):
    w, z, sg, cnt, live = (np.asarray(c) for c in
                           scal_cols(store.param, store.state))
    return w, z, sg, cnt, live, np.asarray(col_V(store.param, store.state))


# --------------------------------------------------------------- parity

def test_fs1_degenerate_mesh_trajectory_byte_equality(rcv1_path):
    """The sharded program path at fs=1 (mesh_force) must be bit-for-bit
    the unsharded path: same per-epoch losses, same final table bytes."""
    ln0, seen0 = _run(rcv1_path)
    ln1, seen1 = _run(rcv1_path, mesh_force=1)
    assert ln0.mesh is None and ln1.mesh is not None
    assert seen0 == seen1          # float equality, not allclose
    for a, b in zip(_state_cols(ln0.store), _state_cols(ln1.store)):
        np.testing.assert_array_equal(a, b)


def test_fs_sharded_training_matches_unsharded(rcv1_path):
    """fs=4 hashed training reproduces the unsharded trajectory (the
    cross-shard gather/scatter collectives are numerically
    transparent), and the table stays in its key-range layout."""
    from jax.sharding import PartitionSpec as P
    ln0, seen0 = _run(rcv1_path)
    ln4, seen4 = _run(rcv1_path, mesh_fs=4)
    np.testing.assert_allclose(seen4, seen0, rtol=1e-5)
    assert ln4.store.fs_count == 4
    assert ln4.store.state.VVg.sharding.spec[0] == "fs" \
        or ln4.store.state.VVg.sharding.spec == P("fs", None)


# ------------------------------------------------------------ make_mesh

def test_make_mesh_multihost_fs_constraint_errors(monkeypatch):
    """The fs axis must stay intra-host (host-complete table) and a
    multi-host mesh must use every device — both fail typed."""
    import jax
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "local_device_count", lambda: 4)
    # ok: fs divides the local device count, every device used
    mesh = make_mesh(dp=2, fs=4)
    assert mesh.shape == {"dp": 2, "fs": 4}
    with pytest.raises(ValueError, match="host-complete"):
        make_mesh(dp=1, fs=8)       # fs spans two hosts
    with pytest.raises(ValueError, match="use every device"):
        make_mesh(dp=2, fs=2)       # 4 of 8 global devices
    with pytest.raises(ValueError, match="power of two"):
        make_mesh(dp=1, fs=3)


def test_hash_capacity_must_divide_fs():
    param = SGDUpdaterParam(V_dim=2, hash_capacity=1002)
    with pytest.raises(ValueError, match="divisible"):
        SlotStore(param, mesh=make_mesh(dp=1, fs=4))
    validate_fs_capacity(1024, 4)   # no raise
    assert fs_shard_bounds(1024, 4) == [(0, 256), (256, 512),
                                        (512, 768), (768, 1024)]


# ----------------------------------------------------- shard checkpoints

def _filled_store(mesh, cap=2048, V_dim=2):
    param = SGDUpdaterParam(V_dim=V_dim, hash_capacity=cap, l1=0.0,
                            V_threshold=0)
    s = SlotStore(param, mesh=mesh)
    rng = np.random.RandomState(7)
    keys = rng.randint(1, 1 << 62, 300).astype(np.uint64)
    s.push(keys, 1, np.ones(len(keys), np.float32))  # counts
    s.push(keys, 3, rng.randn(len(keys)).astype(np.float32),
           rng.randn(len(keys), V_dim).astype(np.float32),
           np.ones(len(keys), np.float32))
    return s


def test_sharded_checkpoint_roundtrip(tmp_path):
    """fs=4 save writes one member per key range + an array-free stub,
    every manifest verifies, and the table round-trips byte-identically
    into sharded AND unsharded stores."""
    from difacto_tpu.utils import manifest as mft
    mesh = make_mesh(dp=1, fs=4)
    s = _filled_store(mesh)
    path = str(tmp_path / "model")
    n = s.save(path, save_aux=True)
    assert n > 0
    for i in range(4):
        sp = fs_shard_path(path, i, 4)
        assert os.path.exists(sp)
        man = mft.verify(sp, require_manifest=True)
        assert man["fs_shard"] == i and man["fs_count"] == 4
    stub_man = mft.verify(path, require_manifest=True)
    assert stub_man["fs_count"] == 4 and stub_man["rows"] == n
    # shard members are not walk-back entry points; the stub is
    assert mft.generation_paths(path) == [path]

    s_sharded = SlotStore(s.param, mesh=mesh)
    assert s_sharded.load(path) == n
    s_flat = SlotStore(s.param)
    assert s_flat.load(path) == n
    for a, b, c in zip(_state_cols(s), _state_cols(s_sharded),
                       _state_cols(s_flat)):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
    # the sharded load landed fs-sharded
    assert s_sharded.state.VVg.sharding.spec[0] == "fs"


def test_sharded_checkpoint_aux_roundtrip_resumes(tmp_path):
    """save_aux=True round-trips the optimizer state (z/sqrt_g/Vg) so a
    sharded interval checkpoint can resume the exact trajectory."""
    mesh = make_mesh(dp=1, fs=2)
    s = _filled_store(mesh)
    path = str(tmp_path / "aux")
    s.save(path, save_aux=True)
    s2 = SlotStore(s.param, mesh=mesh)
    s2.load(path, weights_only=False)
    _, z1, sg1, _, _, _ = _state_cols(s)
    _, z2, sg2, _, _, _ = _state_cols(s2)
    assert z1.any() and sg1.any()
    np.testing.assert_array_equal(z1, z2)
    np.testing.assert_array_equal(sg1, sg2)


def test_corrupt_one_shard_fails_typed_and_walks_back(tmp_path):
    """A bit flip inside ONE shard member: store.load raises the typed
    CheckpointCorrupt BEFORE any state commits, and the serve open path
    walks the family back to the previous verified generation."""
    from difacto_tpu.serve.model import open_serving_store
    mesh = make_mesh(dp=1, fs=4)
    s = _filled_store(mesh)
    path = str(tmp_path / "model")
    s.save(path)                                   # generation 1 (good)
    s.push(np.array([123456789], np.uint64), 3,
           np.ones(1, np.float32), np.ones((1, 2), np.float32),
           np.ones(1, np.float32))
    s.save(path + "_iter-1")                       # generation 2
    sp = fs_shard_path(path + "_iter-1", 2, 4)
    with open(sp, "r+b") as f:
        data = f.read()
        f.seek(data.find(b"w.npy") + 200)
        f.write(b"\xff\xff\xff")
    fresh = SlotStore(s.param, mesh=mesh)
    with pytest.raises(CheckpointCorrupt):
        fresh.load(path + "_iter-1")
    # serve startup walks back to generation 1 instead of dying
    store, meta, _ = open_serving_store(path + "_iter-1",
                                        [("serve_mesh_fs", "2")])
    assert meta["path"] == path
    assert store.fs_count == 2


def test_two_host_sim_sharded_saves_roundtrip(tmp_path):
    """Multi-host × fs>1 per-shard saves (PR 12 leftover, ISSUE 13
    satellite): every rank writes its OWN ``<model>_part-<rank>``
    sharded family (the table is host-complete — fs stays intra-host,
    dp replicates it across hosts, parallel/mesh.py), so ANY rank's
    family restores the full table into any mesh. Simulated with two
    stores holding the identical dp-replicated state:

    - both ranks' families verify independently (members + stub);
    - rank 1's family loads byte-identically to rank 0's, into fs=2,
      fs=4 AND fs=1 (unsharded) stores;
    - a corrupt shard member in rank 0's family fails typed, and the
      resume walk order (learners/sgd._try_resume: own rank first,
      then every rank) lands on rank 1's intact family."""
    import jax

    from difacto_tpu.utils import manifest as mft
    mesh = make_mesh(dp=1, fs=2)
    s0 = _filled_store(mesh)
    s1 = SlotStore(s0.param, mesh=mesh)
    # rank 1 holds the same dp-replicated state
    s1.state = jax.tree_util.tree_map(lambda x: x, s0.state)
    base = str(tmp_path / "model_iter-0_part-")
    n0 = s0.save(base + "0", save_aux=True, epoch=0)
    n1 = s1.save(base + "1", save_aux=True, epoch=0)
    assert n0 == n1 > 0
    for rank in (0, 1):
        for i in range(2):
            man = mft.verify(fs_shard_path(base + str(rank), i, 2),
                             require_manifest=True)
            assert man["fs_count"] == 2 and man["fs_shard"] == i
        assert mft.verify(base + str(rank),
                          require_manifest=True)["fs_count"] == 2

    loads = []
    for fs, m in ((2, mesh), (4, make_mesh(dp=1, fs=4)), (1, None)):
        fresh = SlotStore(s0.param, mesh=m)
        assert fresh.load(base + "1", weights_only=False) == n0
        loads.append(fresh)
    for a, b, c in zip(_state_cols(s0), _state_cols(loads[0]),
                       _state_cols(loads[2])):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)

    # torn rank-0 family: bit-flip one shard member, walk to rank 1
    sp = fs_shard_path(base + "0", 1, 2)
    with open(sp, "r+b") as f:
        data = f.read()
        f.seek(data.find(b"w.npy") + 150)
        f.write(b"\xff\xff\xff")
    resumed = None
    fresh = SlotStore(s0.param, mesh=mesh)
    for rank in (0, 1):        # the _try_resume walk order
        try:
            fresh.load(base + str(rank), require_manifest=True)
            resumed = rank
            break
        except (FileNotFoundError, OSError):
            continue
        except CheckpointCorrupt:
            continue
    assert resumed == 1
    for a, b in zip(_state_cols(s0), _state_cols(fresh)):
        np.testing.assert_array_equal(a, b)


def test_missing_shard_member_is_corrupt(tmp_path):
    mesh = make_mesh(dp=1, fs=2)
    s = _filled_store(mesh)
    path = str(tmp_path / "model")
    s.save(path)
    os.remove(fs_shard_path(path, 1, 2))
    os.remove(fs_shard_path(path, 1, 2) + ".manifest.json")
    with pytest.raises(CheckpointCorrupt, match="missing"):
        SlotStore(s.param, mesh=mesh).load(path)


# ---------------------------------------------------------------- serve

def test_serve_fs_sharded_scores_byte_identical(rcv1_path, tmp_path):
    """Train (fs-sharded), save (per-shard), then serve the model at
    serve_mesh_fs in {1, 2, 4}: scores are byte-identical across serve
    layouts — the end-to-end 'trains AND serves' acceptance leg."""
    from difacto_tpu.data.reader import Reader
    from difacto_tpu.serve.executor import PredictExecutor
    from difacto_tpu.serve.model import open_serving_store
    model = str(tmp_path / "model")
    ln, _ = _run(rcv1_path, mesh_fs=2, model_out=model)
    assert os.path.exists(model + "_part-0_fs-0-of-2")

    blk = next(iter(Reader(rcv1_path, "libsvm", 0, 1)))
    scores = {}
    for fs in (1, 2, 4):
        store, meta, _ = open_serving_store(
            model, [("serve_mesh_fs", str(fs))])
        assert store.fs_count == fs and store.read_only
        ex = PredictExecutor(store)
        scores[fs] = ex.predict(blk)[0]
        assert ex.stats()["dispatches"] == 1
    assert scores[1].any()
    np.testing.assert_array_equal(scores[1], scores[2])
    np.testing.assert_array_equal(scores[1], scores[4])


def test_hot_reload_geometry_check_covers_fs(tmp_path):
    """An in-place store swap must keep the fs degree (the compiled
    programs bake the layout); run_serve threads serve_mesh_fs through
    the reloader kwargs so reloads keep the mesh."""
    from difacto_tpu.serve.executor import PredictExecutor
    from difacto_tpu.serve.model import open_serving_store
    from difacto_tpu.serve.reload import ModelReloader
    mesh = make_mesh(dp=1, fs=2)
    s = _filled_store(mesh)
    path = str(tmp_path / "model")
    s.save(path)
    store, _, _ = open_serving_store(path, [("serve_mesh_fs", "2")])
    ex = PredictExecutor(store)
    flat, _, _ = open_serving_store(path, [])
    with pytest.raises(ValueError, match="fs=2"):
        ex.swap_store(flat)
    # a reload with the same kwargs rebuilds the same mesh and succeeds
    rl = ModelReloader(ex, path, kwargs=[("serve_mesh_fs", "2")])
    s.save(path)    # bump generation
    res = rl.reload()
    assert res["ok"], res
    assert ex.store.fs_count == 2


def test_run_serve_threads_mesh_into_reloader(rcv1_path, tmp_path):
    """Wire-level leg: task=serve with serve_mesh_fs=2 scores over TCP
    from per-shard checkpoint files, and a `#reload` rebuilds the SAME
    fs-sharded mesh (run_serve passes the store kwargs to the
    ModelReloader — a reload that silently de-sharded the table was the
    exact regression this test pins)."""
    import threading
    import time
    from difacto_tpu.serve import ServeClient, run_serve
    model = str(tmp_path / "model")
    ln, _ = _run(rcv1_path, mesh_fs=2, model_out=model)
    ready = str(tmp_path / "ready")
    t = threading.Thread(target=run_serve, args=([
        ("model_in", model), ("serve_mesh_fs", "2"),
        ("serve_ready_file", ready), ("serve_max_seconds", "8"),
        ("serve_batch_size", "100"), ("serve_max_delay_ms", "50")],),
        daemon=True)
    t.start()
    deadline = time.monotonic() + 15
    while not os.path.exists(ready):
        assert time.monotonic() < deadline, "server never became ready"
        time.sleep(0.05)
    host, port = open(ready).read().split()
    lines = [ln_.encode() for ln_
             in open(rcv1_path).read().splitlines()[:20]]
    with ServeClient(host, int(port)) as c:
        out = c.score_lines(lines)
        res = c.reload()
    assert len(out) == 20 and not any(o.startswith(b"!") for o in out)
    assert res["ok"], res
    t.join(timeout=30)


# ------------------------------------------------- stats + capacity legs

def test_shard_stats_and_gauges():
    mesh = make_mesh(dp=1, fs=4)
    s = _filled_store(mesh)
    stats = s.shard_stats()
    assert [st["shard"] for st in stats] == [0, 1, 2, 3]
    w = _state_cols(s)[0]
    assert sum(st["rows"] for st in stats) == int((w != 0).sum()) > 0
    per_dev = state_bytes(s.param, s.state.capacity) // 4
    assert all(st["table_bytes"] == per_dev for st in stats)
    published = s.publish_shard_stats()
    assert published == stats
    from difacto_tpu.obs import REGISTRY
    snap = REGISTRY.snapshot()["gauges"].get("store_shard_rows", {})
    assert snap, "store_shard_rows gauge not published"
    assert sum(snap.values()) == sum(st["rows"] for st in stats)


def test_capacity_scaling_report_legs():
    from difacto_tpu.parallel.capacity import capacity_scaling_report
    rep = capacity_scaling_report(fs_values=[1, 2], base_capacity=512,
                                  V_dim=2, batch=64, nnz_per_row=4,
                                  steps=2)
    assert [leg["fs"] for leg in rep["legs"]] == [1, 2]
    l1, l2 = rep["legs"]
    assert l2["hash_capacity"] == 2 * l1["hash_capacity"]
    assert l2["table_bytes_per_device"] == l1["table_bytes_per_device"]
    assert rep["capacity_scaling"] == 2.0
    assert rep["scaling_efficiency"] == 1.0
    assert all(leg["examples_per_sec"] > 0 for leg in rep["legs"])


def test_bounded_delay_report_refuses_a_mesh_it_cannot_build():
    """fs beyond the visible devices used to be clamped silently — a
    different measurement under the same name; now it raises."""
    from difacto_tpu.parallel.capacity import bounded_delay_report
    with pytest.raises(ValueError, match="fs=16 needs 16 devices"):
        bounded_delay_report(fs=16)


def test_mesh_store_is_built_sharded():
    """The initial table comes out of one jitted program; under a mesh
    it is pinned to the fs layout (no device ever holds the whole
    table) with the same values as the unsharded init, which are the
    eager init_state's."""
    param = SGDUpdaterParam(V_dim=4, hash_capacity=1 << 10, seed=3)
    flat = SlotStore(param)
    sharded = SlotStore(param, mesh=make_mesh(dp=1, fs=4))
    vvg = sharded.state.VVg
    assert len({s.device for s in vvg.addressable_shards}) == 4
    assert all(s.data.shape[0] == (1 << 10) // 4
               for s in vvg.addressable_shards)
    from difacto_tpu.updaters.sgd_updater import init_state
    for eager, a, b in zip(init_state(param, 1 << 10), flat.state,
                           sharded.state):
        np.testing.assert_array_equal(np.asarray(eager), np.asarray(a))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("fs", [1, 4])
def test_tables_load_in_slabs_bit_identically(tmp_path, monkeypatch, fs):
    """A checkpoint is assembled a slab of rows at a time into a
    destination born sharded (the one-shot build of a 2^23-row table fit
    on no chip) — the saved table's bits whatever the slab size, in the
    store's layout, from a per-shard family into any mesh."""
    from difacto_tpu.store import local
    path = str(tmp_path / "model")
    saved = _filled_store(make_mesh(dp=1, fs=4))
    saved.save(path, save_aux=True)
    mesh = make_mesh(dp=1, fs=fs) if fs > 1 else None
    one_slab = SlotStore(saved.param, mesh=mesh)
    layout = one_slab.state.VVg.sharding
    one_slab.load(path)
    monkeypatch.setattr(local, "_SLAB_ROWS", 512)   # 4 slabs
    slabs = SlotStore(saved.param, mesh=mesh)
    slabs.load(path)
    for loaded in (one_slab, slabs):
        assert loaded.state.VVg.sharding == layout
        np.testing.assert_array_equal(np.asarray(loaded.state.VVg),
                                      np.asarray(saved.state.VVg))
