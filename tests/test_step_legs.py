"""ISSUE 25: the step's legs and the replay path's accounting.

(b) the compiled step programs carry every leg name in their HLO
    ``op_name`` metadata, and a trajectory through the streamed step, the
    chunked step and the paired replay program is bit-identical to one
    traced with the scopes stubbed out (scopes are names, not arithmetic);
(c) a replayed epoch that pairs counts every step into
    ``store_gather_bytes_total{path=train}`` and ``train_step_seconds``,
    ``step`` is ``dispatch`` + ``fetch_wait``, and ``epoch_turn`` turns
    once an epoch.
"""

import contextlib
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import write_uniform_libsvm

from difacto_tpu.learners import Learner
from difacto_tpu.obs import names, trace

STEP_LEGS = (names.UNPACK, names.GATHER, names.FORWARD, names.BACKWARD,
             names.UPDATE, names.SCATTER)


def _learner(data, **over):
    args = dict(data_in=data, V_dim=4, V_threshold=0, lr=0.1, l1=1e-4,
                l2=0, num_jobs_per_epoch=1, batch_size=32,
                max_num_epochs=4, shuffle=0, report_interval=0,
                stop_rel_objv=0, hash_capacity=2048,
                producer_mode="thread", device_cache_mb=16)
    args.update(over)
    ln = Learner.create("sgd")
    assert ln.init([(k, str(v)) for k, v in args.items()]) == []
    return ln


def _wait_pair_compile():
    for t in threading.enumerate():
        if t.name == "pair-exec-compile":
            t.join()


def _legs_in(hlo_text: str) -> set:
    found = set()
    for op_name in re.findall(r'op_name="([^"]+)"', hlo_text):
        # the final component is the primitive's own name ("gather" and
        # "scatter" are also primitives)
        found.update(p for p in op_name.split("/")[:-1] if p in names.LEGS)
    return found


def _sds(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


@pytest.mark.parametrize("program", ["_packed_panel_train",
                                     "_packed_panel_train_chunked2"])
def test_compiled_step_carries_every_leg(tmp_path, program):
    path = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=64)
    ln = _learner(path)
    b, w, u = 32, 8, 128
    state = _sds(ln.store.state)
    i32 = jax.ShapeDtypeStruct((b * w + u + 2,), jnp.int32)
    f32 = jax.ShapeDtypeStruct((b * w + 3 * b + u,), jnp.float32)
    if program == "_packed_panel_train":
        low = ln._packed_panel_train.lower(state, i32, f32, b, w, u, True,
                                           False)
    else:
        chunks = jax.eval_shape(
            lambda a, c: ln._panel_chunk_packed(a, c, b, w, u, False),
            i32, f32)
        pa = (i32, f32, chunks)
        low = ln._packed_panel_train_chunked2.lower(state, pa, pa, b, w, u,
                                                    False, False)
    # the legs are in the program text too (frontend attributes), so the
    # persistent compile cache's key follows them; metadata alone is
    # stripped from the key
    stablehlo = low.as_text()
    for leg in STEP_LEGS:
        assert f'leg = "{leg}"' in stablehlo, leg
    assert _legs_in(low.compile().as_text()) >= set(STEP_LEGS)


def test_evaluate_program_carries_its_leg(tmp_path):
    path = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=64)
    ln = _learner(path)
    ln.store.evaluate_dev()
    low = ln.store._eval_jit.lower(_sds(ln.store.state))
    assert names.EVALUATE in _legs_in(low.compile().as_text())


def _trajectory(data, stub: bool, monkeypatch):
    """Losses an epoch and the table's bits after 4 epochs: epoch 0
    stages through the chunked step, later epochs replay in pairs."""
    if stub:
        monkeypatch.setattr(names, "scope",
                            lambda name: contextlib.nullcontext())
    ln = _learner(data)
    seen = []

    def on_end(epoch, train, _val):
        seen.append(train.loss)
        _wait_pair_compile()

    ln.add_epoch_end_callback(on_end)
    ln.run()
    assert ln._paired_dispatches > 0
    monkeypatch.undo()
    return seen, np.asarray(ln.store.state.VVg).view(np.uint32).copy()


def test_scopes_change_no_arithmetic(tmp_path, monkeypatch):
    path = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=96)
    loss_scoped, bits_scoped = _trajectory(path, False, monkeypatch)
    loss_plain, bits_plain = _trajectory(path, True, monkeypatch)
    assert loss_scoped == loss_plain and len(loss_scoped) == 4
    np.testing.assert_array_equal(bits_scoped, bits_plain)


def test_streamed_step_same_with_scopes_stubbed(tmp_path, monkeypatch):
    """Three streamed steps through ``_packed_panel_train`` (no cache)."""
    path = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=96)

    def run(stub):
        if stub:
            monkeypatch.setattr(names, "scope",
                                lambda name: contextlib.nullcontext())
        ln = _learner(path, device_cache_mb=0, max_num_epochs=1)
        seen = []
        ln.add_epoch_end_callback(lambda e, t, v: seen.append(t.loss))
        ln.run()
        monkeypatch.undo()
        return seen, np.asarray(ln.store.state.VVg).view(np.uint32).copy()

    (l0, b0), (l1, b1) = run(False), run(True)
    assert l0 == l1
    np.testing.assert_array_equal(b0, b1)


# --------------------------------------------------- the replay path (c)
def _snap(ln) -> dict:
    snap = ln.obs.snapshot()
    stages = {dict(k)["stage"]: v for k, v in
              snap["counters"]["stage_seconds_total"].items()}
    return {"stages": stages,
            "gather": ln.obs.value("store_gather_bytes_total",
                                   path="train"),
            "steps_timed": snap["hists"]["train_step_seconds"][()]["count"],
            "paired": getattr(ln, "_paired_dispatches", 0)}


def test_replayed_epoch_with_pairs_is_accounted(tmp_path):
    from difacto_tpu.updaters.sgd_updater import gather_bytes
    path = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=128)
    ln = _learner(path, max_num_epochs=5)
    steps = 128 // 32
    at_end = []

    def on_end(epoch, train, _val):
        _wait_pair_compile()
        at_end.append(_snap(ln))

    ln.add_epoch_end_callback(on_end)
    trace.drain_events()
    trace.start()
    try:
        ln.run()
    finally:
        trace.stop()
    events = trace.drain_events()

    # the last epoch ran wholly in pairs
    before, after = at_end[-2], at_end[-1]
    assert after["paired"] - before["paired"] == steps // 2
    u_cap = next(iter(ln._pair_execs))[2]
    per_step = 2 * gather_bytes(ln.store.param, ln.store.state.capacity,
                                u_cap)
    assert after["gather"] - before["gather"] == steps * per_step
    assert after["steps_timed"] - before["steps_timed"] == steps
    d = {k: after["stages"][k] - before["stages"].get(k, 0.0)
         for k in after["stages"]}
    assert d["dispatch"] > 0 and d["fetch_wait"] > 0
    assert d["epoch_turn"] > 0
    assert d["compile"] == 0.0
    # one helper produces the three: they cannot drift
    total = after["stages"]
    assert total["step"] == pytest.approx(
        total["dispatch"] + total["fetch_wait"], rel=1e-9)

    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    n_epochs = len(by_name[names.EPOCH])
    assert n_epochs == 5
    # one turn an epoch (the last is closed by stop()), each holding its
    # children; the dispatches of the paired epochs carry two steps each
    assert len(by_name[names.EPOCH_TURN]) == n_epochs
    for child in (names.TURN_MERGE, names.TURN_EVAL, names.TURN_EVICT,
                  names.TURN_CALLBACKS):
        assert len(by_name[child]) == n_epochs, child
    assert len(by_name[names.TURN_ITER_PARTS]) == n_epochs - 1
    turn_ids = {e["args"]["span_id"] for e in by_name[names.EPOCH_TURN]}
    assert all(e["args"]["parent"] in turn_ids
               for e in by_name[names.TURN_EVAL])
    last = [e for e in by_name[names.DISPATCH]
            if e["args"]["epoch"] == n_epochs - 1]
    assert [e["args"]["step_num"] for e in last] == [0, 2]
    assert names.COMPILE_PAIR in by_name
