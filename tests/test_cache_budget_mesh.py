"""ISSUE 29: the replay cache's budget under a mesh, and the exchange's
counter.

``device_cache_mb`` is a per-host budget and a staged batch is charged
one copy for every addressable device, so under ``mesh_fs=4`` (batch
arrays replicated over fs) an epoch that fits the budget on one device
needs four times the budget. A cache that freezes with nothing kept says
so once, at warning level, sets ``device_cache_state{job}`` to 0 (off),
and the run streams to the same loss; at four times the budget it
replays. ``store_exchange_bytes_total{path=train}`` stays 0 without a
mesh and adds row cap x lanes x item size a step with one.
"""

import contextlib
import logging

import jax
import pytest
from conftest import write_uniform_libsvm

from difacto_tpu.learners import Learner
from difacto_tpu.learners.sgd import K_TRAINING
from difacto_tpu.updaters.sgd_updater import gather_bytes

ROWS, BATCH, EPOCHS = 2048, 256, 3
STEPS = ROWS // BATCH
FS = 4
# one device is charged 4.0 MB for the epoch (3.8 MB before ISSUE 30: a
# staged batch now carries its head rows, 4 bytes a lane, and shapes
# this small keep the static chunk bound), four replicas 15.6 MB
FITS_ONE, FITS_FOUR = 5, 16


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    return write_uniform_libsvm(
        str(tmp_path_factory.mktemp("budget") / "u.libsvm"), rows=ROWS,
        width=16, id_space=3000)


def _run(data, caplog=None, **over):
    if over.get("mesh_fs", 1) > len(jax.devices()):
        pytest.skip("needs four (virtual) devices")
    args = dict(data_in=data, V_dim=4, V_threshold=0, lr=0.1, l1=1e-4,
                l2=0, num_jobs_per_epoch=1, batch_size=BATCH,
                max_num_epochs=EPOCHS, shuffle=0, report_interval=0,
                stop_rel_objv=0, hash_capacity=4096,
                producer_mode="thread")
    args.update(over)
    ln = Learner.create("sgd")
    assert ln.init([(k, str(v)) for k, v in args.items()]) == []
    losses = []
    ln.add_epoch_end_callback(
        lambda _k, train, _val: losses.append(float(train.loss)))
    with (caplog.at_level(logging.INFO, logger="difacto_tpu") if caplog
          else contextlib.nullcontext()):
        ln.run()
    said = [r for r in caplog.records
            if "device batch cache" in r.message] if caplog else []
    return ln, losses, said


def _state(ln):
    return (ln.obs.value("device_cache_state", job="train"),
            ln.obs.value("device_cache_staged_bytes", job="train"))


@pytest.fixture(scope="module")
def streamed_losses(data):
    """The trajectory with the cache off: what every budget must give."""
    return _run(data, device_cache_mb=0)[1]


@pytest.mark.parametrize("mesh_fs, budget, state", [
    (1, FITS_ONE, "complete"),
    (FS, FITS_ONE, "off"),          # four replicas do not fit: silent before
    (FS, FITS_FOUR, "complete"),    # the per-host budget: four times
    (1, 1, "off"),
], ids=["one_device_fits", "mesh_same_budget_off", "mesh_four_times_fits",
        "one_device_too_small"])
def test_budget_is_per_host_and_an_empty_cache_is_loud(
        data, caplog, streamed_losses, mesh_fs, budget, state):
    ln, losses, said = _run(data, caplog, mesh_fs=mesh_fs,
                            device_cache_mb=budget)
    info = ln.device_cache_info()[K_TRAINING]
    assert info["complete"] is (state == "complete")
    assert info["frozen"] is False
    # the run trains to the same loss whether it replays or streams
    assert losses == pytest.approx(streamed_losses, rel=1e-5)
    gauge, staged = _state(ln)
    warnings = [r for r in said if r.levelno == logging.WARNING]
    if state == "complete":
        assert gauge == 2 and staged == ln._dev_caches[K_TRAINING].used > 0
        assert info["charged_bytes_needed"] == 0 and not warnings
        assert info["staged_mb"] <= budget
    else:
        assert gauge == 0 and staged == 0 and info["staged_parts"] == 0
        # once, with what the part needed as charged, the budget, the
        # placement and the budget that would have held it
        assert len(warnings) == 1
        text = warnings[0].getMessage()
        need = info["charged_bytes_needed"]
        assert need > budget << 20
        would = -(-need // (1 << 20))
        assert f"device_cache_mb={budget} " in text
        assert f"device_cache_mb>={would} " in text
        assert f"{STEPS} batches" in text
        assert ("dp=1 x fs=4" in text) is (mesh_fs == FS)
        assert not ln._dev_caches[K_TRAINING].alive


def test_charged_bytes_are_mesh_fs_times_the_one_device_bytes(data, caplog):
    ln, _, _ = _run(data, caplog, mesh_fs=FS, device_cache_mb=FITS_FOUR)
    cache = ln._dev_caches[K_TRAINING]
    items = [pl for part in cache.entries.values() for pl in part]
    assert len(items) == STEPS and all(pl[0] == "devbatch" for pl in items)
    one_copy = sum(x.nbytes for pl in items
                   for x in jax.tree_util.tree_leaves((pl[1], pl[2])))
    assert cache.used == FS * one_copy
    # and that is what the empty cache says it needed
    off, _, _ = _run(data, caplog, mesh_fs=FS, device_cache_mb=FITS_ONE)
    assert off.device_cache_info()[K_TRAINING]["charged_bytes_needed"] \
        == FS * one_copy


def test_a_kept_prefix_is_said_at_info(data, caplog):
    """Two parts, a budget for one: the first replays, the second streams;
    no warning."""
    ln, _, said = _run(data, caplog, num_jobs_per_epoch=2,
                       device_cache_mb=3)
    info = ln.device_cache_info()[K_TRAINING]
    assert info["frozen"] and not info["complete"]
    assert info["staged_parts"] == 1
    assert info["charged_bytes_needed"] > 0
    assert _state(ln)[0] == 1
    assert [r.levelno for r in said] == [logging.INFO]
    assert "1 staged part(s) replay" in said[0].getMessage()


@pytest.mark.parametrize("mesh_fs", [1, FS], ids=["no_mesh", "mesh_fs4"])
def test_exchange_counter_counts_the_all_reduce_operand(data, caplog,
                                                        mesh_fs):
    ln, _, _ = _run(data, caplog, mesh_fs=mesh_fs,
                    device_cache_mb=FITS_FOUR)
    got = ln.obs.value("store_exchange_bytes_total", path="train")
    if mesh_fs == 1:
        assert got == 0
        return
    u_cap = ln._shapes.snapshot()["train.u"]
    a_step = gather_bytes(ln.store.param, ln.store.state.capacity, u_cap)
    assert a_step == u_cap * ln.store.state.VVg.shape[1] \
        * ln.store.state.VVg.dtype.itemsize
    # streamed epoch 0 and the replayed epochs alike, the pull alone
    assert got == EPOCHS * STEPS * a_step
    assert ln.obs.value("store_gather_bytes_total", path="train") \
        == 2 * got
