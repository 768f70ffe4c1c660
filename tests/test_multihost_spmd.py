"""Multi-host x mesh synchronized stepping (round-1 verdict item 4).

Two processes launched through launch.py, each with 4 virtual CPU devices,
train over a global (dp=2, fs=4) mesh with the hashed store. The per-step
global batch is the union of both hosts' local batches, so the trajectory
must match a single-host run over the same data with the same
hash_capacity (reference analog: ps-lite rendezvous + synchronized
barriers, src/store/kvstore_dist.h:61-70)."""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from conftest import two_process_launch

pytestmark = two_process_launch

REPO = pathlib.Path(__file__).resolve().parent.parent
EPOCHS = 4


def _single_host_reference(rcv1_path, data_val, **overrides):
    from difacto_tpu.learners import Learner
    conf = {"data_in": rcv1_path, "V_dim": "2", "V_threshold": "2",
            "lr": "0.1", "l1": "0.1", "l2": "0",
            "batch_size": "100", "max_num_epochs": str(EPOCHS),
            "shuffle": "0", "report_interval": "0",
            "stop_rel_objv": "0", "stop_val_auc": "-2",
            "num_jobs_per_epoch": "1",
            "hash_capacity": str(1 << 20)}
    if data_val:
        conf["data_val"] = data_val
    conf.update({k: str(v) for k, v in overrides.items()})
    ln = Learner.create("sgd")
    ln.init(list(conf.items()))
    seen, seen_val = [], []
    ln.add_epoch_end_callback(
        lambda e, t, v: (seen.append(t.loss), seen_val.append(v.loss)))
    ln.run()
    return seen, seen_val


def test_two_process_mesh_matches_single_host(rcv1_path, tmp_path):
    # validation file of 300 rows: eval Reader chunks (256MB => whole file)
    # exceed b_cap=bucket(100)=128, so the SPMD eval path must slice them
    # into batch_size windows (advisor round-2 medium finding)
    val_path = str(tmp_path / "val300.libsvm")
    text = open(rcv1_path).read()
    with open(val_path, "w") as f:
        f.write(text * 3)

    trajs = _launch_two(tmp_path, rcv1_path, EPOCHS, 7921,
                        data_val=val_path)
    # both ranks observed the identical global trajectory
    np.testing.assert_allclose(trajs[0]["train"], trajs[1]["train"],
                               rtol=0, atol=0)
    np.testing.assert_allclose(trajs[0]["val"], trajs[1]["val"],
                               rtol=0, atol=0)
    assert len(trajs[0]["train"]) == EPOCHS

    # and it matches the single-host run over the same data: each host read
    # half the file (byte-range parts), the per-step union batch = the
    # single host's 100-row batch. Validation loss is a pure sum over rows,
    # so it is chunking-invariant and must match too.
    ref, ref_val = _single_host_reference(rcv1_path, val_path)
    np.testing.assert_allclose(trajs[0]["train"], ref, rtol=2e-4)
    np.testing.assert_allclose(trajs[0]["val"], ref_val, rtol=2e-4)

    # per-rank checkpoints were written by both hosts
    assert (tmp_path / "model_part-0").exists()
    assert (tmp_path / "model_part-1").exists()


def _launch_two(tmp_path, data, epochs, port, extra=(), data_val=""):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # worker sets its own 4-device flag
    env["PYTHONPATH"] = str(REPO)
    proc = subprocess.run(
        [sys.executable, str(REPO / "launch.py"), "-n", "2",
         "--port", str(port), "--",
         sys.executable, str(REPO / "tests" / "spmd_worker.py"),
         str(tmp_path), data, str(epochs), data_val, *extra],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\n" \
                                 f"stderr:\n{proc.stderr}"
    trajs = []
    for rank in range(2):
        with open(tmp_path / f"traj-{rank}.json") as f:
            trajs.append(json.load(f))
    return trajs


def test_two_process_dictionary_matches_single_host(rcv1_path, tmp_path):
    """Exact-id dictionary store over two hosts (round-4 missing #1: the
    reference keys its distributed model by exact 64-bit feature id,
    src/sgd/sgd_updater.h:141-176 — no two features ever alias). The
    control plane ships raw ids; every host inserts the identical sorted
    union, so replica dictionaries stay bit-identical. V_dim=0 makes the
    trajectory slot-numbering-invariant, so the 2-process run must match
    a single-host dictionary run."""
    trajs = _launch_two(tmp_path, rcv1_path, EPOCHS, 7927,
                        extra=["hash_capacity=0", "V_dim=0"])
    np.testing.assert_allclose(trajs[0]["train"], trajs[1]["train"],
                               rtol=0, atol=0)
    # replica-dictionary invariants: identical id->slot maps and capacity
    assert trajs[0]["num_features"] == trajs[1]["num_features"] > 0
    assert trajs[0]["capacity"] == trajs[1]["capacity"]
    # passes after the first ship int32 slots instead of uint64 ids
    # (half the control bytes); both ranks took that branch
    assert trajs[0]["slot_steps"] > 0 and trajs[1]["slot_steps"] > 0

    ref, _ = _single_host_reference(rcv1_path, "", hash_capacity=0,
                                    V_dim=0)
    np.testing.assert_allclose(trajs[0]["train"], ref, rtol=2e-4)


def test_two_process_dictionary_growth_and_embeddings(rcv1_path, tmp_path):
    """Dictionary SPMD with embeddings and a small init_capacity: the
    table must grow by doubling mid-epoch-0 through the DEFERRED growth
    path (exchange() computes OOB padding against the capacity the
    dispatch thread will have; grow_to applies it in step order). Ranks
    must stay bit-identical and the objective must fall. The rcv1
    fixture has 2775 distinct features, so init_capacity=1024 forces
    1024 -> 4096."""
    trajs = _launch_two(tmp_path, rcv1_path, 3, 7929,
                        extra=["hash_capacity=0", "init_capacity=1024"])
    np.testing.assert_allclose(trajs[0]["train"], trajs[1]["train"],
                               rtol=0, atol=0)
    assert trajs[0]["num_features"] == trajs[1]["num_features"] == 2775
    assert trajs[0]["capacity"] == trajs[1]["capacity"] == 4096
    losses = trajs[0]["train"]
    assert losses[-1] < losses[0]


def test_two_process_mesh_panel_path(tmp_path):
    """Uniform-width data engages the SPMD panel + chunked-run step
    (round-5: the synchronized schedule previously always built COO
    batches and took the unsorted backward). Both ranks must agree on
    the global panel decision, observe the identical trajectory, and
    match a single-host run over the same data."""
    from conftest import write_uniform_libsvm
    data = write_uniform_libsvm(tmp_path / "uniform.libsvm", rows=100)

    trajs = _launch_two(tmp_path, data, 3, 7925)
    assert trajs[0]["panel_steps"] > 0 and trajs[1]["panel_steps"] > 0
    np.testing.assert_allclose(trajs[0]["train"], trajs[1]["train"],
                               rtol=0, atol=0)

    from difacto_tpu.learners import Learner
    ln = Learner.create("sgd")
    ln.init([("data_in", data), ("V_dim", "2"), ("V_threshold", "2"),
             ("lr", "0.1"), ("l1", "0.1"), ("l2", "0"),
             ("batch_size", "100"), ("max_num_epochs", "3"),
             ("shuffle", "0"), ("report_interval", "0"),
             ("stop_rel_objv", "0"), ("stop_val_auc", "-2"),
             ("num_jobs_per_epoch", "1"), ("hash_capacity", str(1 << 20))])
    seen = []
    ln.add_epoch_end_callback(lambda e, t, v: seen.append(t.loss))
    ln.run()
    np.testing.assert_allclose(trajs[0]["train"], seen, rtol=2e-4)


def test_rank_that_cannot_bind_a_device_takes_the_job_down(rcv1_path):
    """One process per chip: on a single TPU host the second local rank
    finds the chip taken. Stood in for here by a rank whose platform has
    no device — it must leave at once and non-zero so the launcher kills
    its peer, instead of both waiting on each other in jax.distributed
    (the peer for this rank's devices, this rank in the shutdown
    barrier) until the 2-minute topology timeout."""
    from difacto_tpu.parallel.multihost import NO_DEVICE_EXIT_CODE
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    worker = ('if [ "$DIFACTO_RANK" = 1 ]; then export JAX_PLATFORMS=tpu; '
              f'fi; exec {sys.executable} -m difacto_tpu task=train '
              f'data_in={rcv1_path} hash_capacity=4096 batch_size=100 '
              'max_num_epochs=1 mesh_dp=2 mesh_fs=1')
    proc = subprocess.run(
        [sys.executable, str(REPO / "launch.py"), "-n", "2",
         "--port", "7953", "--", "sh", "-c", worker],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=60)
    assert proc.returncode == NO_DEVICE_EXIT_CODE, proc.stderr[-2000:]
    assert "cannot bind a device" in proc.stderr
