"""ISSUE 37: the activation rule, which is what DiFacto is, held to the
plain reference row for row.

A feature gets an embedding only once its count passes ``V_threshold``
*and* l1 has left its ``w`` non-zero (``v_live |= (w != 0) & (cnt >
thr)``, re-evaluated after every count push and every gradient push);
with ``l1_shrk`` an embedding whose ``w`` is 0 is masked out of the pull.
The benchmark's ``fm_v64_avazu.replay_avazu`` cell runs the gates at the
upstream's defaults on the chip and compares sums over the touched rows;
what sums cannot see is guarded here, at a tiny size on the CPU, against
``perfbench/reference.py`` (nothing of the program imported there):

(a) the live set equal row for row after every step of epoch 0, while
    rows cross the threshold at different steps, and after every replayed
    epoch; V of rows that never went live bit-identical to the seed's;
(b) counts are pushed in epoch 0 only;
(c) a live row whose ``w`` returns to 0 keeps ``live`` and is masked
    under ``l1_shrk`` (its V stands still), and served without it;
(d) one call of the count-free pair-replay program equals two one-batch
    replayed steps, bit for bit, with the gates on;
(e) the gauge ``model_live_V`` is the table's own ``v_live`` column.

(a) and (b) also under ``mesh_fs = 2`` and with ``slot_dtype = int8``.
"""

import os
import re
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CAP, BATCH, STEPS, EPOCHS = 4096, 64, 8, 3
# Avazu's shape in small: a few fields of a handful of values (runs of
# tens of tokens a batch: over the threshold in step 1) beside wide ones
# (a token seen now and then: over it steps later, or never)
GENERATOR = dict(int_fields=0, cat_fields=6,
                 cat_tokens=[4, 7, 9, 50, 300, 2000], zipf_a=1.1,
                 v_fields=4, v_rank=4, ctr=0.3)
CONFIG = dict(loss="fm", V_dim=8, V_dtype="float32", V_threshold=10,
              l1_shrk=1, l1=1, l2=0, V_l2=0.01, V_lr=0.01,
              V_init_scale=0.01, lr=0.1, batch_size=BATCH,
              data_format="rec", hash_capacity=CAP)
TRAFFIC = dict(rows_per_epoch=BATCH * STEPS, generator=GENERATOR,
               learner=dict(device_cache_mb=16, shuffle=0, stop_rel_objv=0,
                            report_interval=0, num_jobs_per_epoch=1,
                            producer_mode="thread"))


def _data(tmp, seed):
    """The rows as the benchmark makes them (``perfbench/run.make_data``:
    one pre-localized rec member a step) and each step's batch as the
    reference takes it: (table rows int32[B, F], labels)."""
    from perfbench import gen, run as R
    os.makedirs(tmp, exist_ok=True)
    data = R.make_data(seed, CONFIG, TRAFFIC, tmp, STEPS)
    batches = [(gen.slots_of(data["tables"].rev_of(g), CAP).astype(np.int32),
                y) for y, g in (data["kept"][m] for m in range(STEPS))]
    return batches


def _columns(learner):
    """The whole table as host arrays: w, cnt, live, and the bits of
    the embedding half of every fused row."""
    from difacto_tpu.updaters.sgd_updater import row_layout, scal_cols
    st = learner.store.state
    param = learner.store.param
    w, _, _, cnt, live = (np.asarray(x) for x in scal_cols(param, st))
    k = row_layout(param, st.capacity)[0]
    emb = np.asarray(st.VVg[:, :k])
    bits = emb.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[
        emb.dtype.itemsize])
    return dict(w=w, cnt=cnt, live=live, bits=bits)


def _run_program(tmp, seed, epochs=EPOCHS, **over):
    """Drive the learner over the rows; keep the table after every
    streamed step of epoch 0 and after every epoch."""
    from perfbench import sut
    from difacto_tpu.learners import Learner
    kwargs = sut.learner_kwargs(CONFIG, TRAFFIC, tmp, seed, over)
    kwargs["max_num_epochs"] = epochs
    ln = Learner.create("sgd")
    assert ln.init([(k, str(v)) for k, v in kwargs.items()]) == []
    seen = {"init": _columns(ln), "steps": [], "epochs": []}
    orig = ln._dispatch_item

    def spy(*a, **kw):
        orig(*a, **kw)
        if len(seen["steps"]) < STEPS:
            seen["steps"].append(_columns(ln))

    def on_end(epoch, _train, _val):
        seen["epochs"].append(_columns(ln))
        for t in threading.enumerate():     # replay in pairs from now on
            if t.name == "pair-exec-compile":
                t.join()

    ln._dispatch_item = spy
    ln.add_epoch_end_callback(on_end)
    ln.run()
    seen["learner"] = ln
    return seen


def _run_reference(seed, batches, epochs=EPOCHS):
    """The reference over the WHOLE tiny table (its rows are the table's,
    so a batch indexes it by the hashed row): epoch 0 pushes counts, the
    replayed epochs push none."""
    from perfbench import reference as ref
    h = ref.Hyper.of(CONFIG)
    V0 = ref.initial_V(seed, CAP, np.arange(CAP), h)
    s = ref.initial_state(V0)
    out = {"V0": np.asarray(V0), "steps": [], "epochs": []}
    for e in range(epochs):
        for idx, y in batches:
            s, _ = ref.step(h, s, jnp.asarray(idx), jnp.asarray(y),
                            push_counts=(e == 0))
            if e == 0:
                out["steps"].append(s)
        out["epochs"].append(s)
    return out


CASES = {
    "one_device": {},
    "mesh_fs2": {"mesh_fs": 2, "device_cache_mb": 32},
    "int8_rows": {"slot_dtype": "int8"},
}


@pytest.fixture(scope="module", params=list(CASES))
def trajectory(request, tmp_path_factory):
    over = CASES[request.param]
    if over.get("mesh_fs", 1) > len(jax.devices()):
        pytest.skip("needs two (virtual) devices")
    seed = 11
    tmp = str(tmp_path_factory.mktemp("act_" + request.param))
    batches = _data(tmp, seed)
    return (request.param, _run_program(tmp, seed, **over),
            _run_reference(seed, batches), batches)


# ---------------------------------------------- (a) the live set, row by row
def test_live_set_equals_the_references_after_every_step(trajectory):
    _, prog, ref, batches = trajectory
    assert len(prog["steps"]) == STEPS == len(ref["steps"])
    crossed = []
    for t, (p, r) in enumerate(zip(prog["steps"], ref["steps"])):
        live = np.asarray(r.live)
        assert np.array_equal(p["live"], live), f"step {t + 1}"
        # the two conditions, on the program's own columns: nothing is
        # live that has not passed the count, nothing without ever
        # having had a weight
        assert not (p["live"] & ~(p["cnt"] > CONFIG["V_threshold"])).any()
        assert np.array_equal(p["cnt"], np.asarray(r.cnt))
        assert np.array_equal(p["w"] != 0, np.asarray(r.w) != 0)
        crossed.append(int(live.sum()))
    # rows cross at different steps: the set grows over several of them,
    # and most touched rows never get an embedding
    assert crossed[0] > 0 and len(set(crossed)) >= 4, crossed
    touched = np.unique(np.concatenate([i.reshape(-1)
                                        for i, _ in batches]))
    assert crossed[-1] < 0.2 * len(touched), (crossed, len(touched))
    # passed the count but no weight survived l1: not live
    last = prog["steps"][-1]
    assert ((last["cnt"] > CONFIG["V_threshold"]) & ~last["live"]).any()


def test_live_set_equals_the_references_after_every_replayed_epoch(
        trajectory):
    _, prog, ref, _ = trajectory
    assert len(prog["epochs"]) == EPOCHS
    grew = []
    for e, (p, r) in enumerate(zip(prog["epochs"], ref["epochs"])):
        assert np.array_equal(p["live"], np.asarray(r.live)), f"epoch {e}"
        grew.append(int(p["live"].sum()))
    # replay pushes no counts, yet a row whose count had passed in epoch
    # 0 goes live the first time l1 leaves its weight non-zero
    assert grew == sorted(grew) and grew[0] > 0


def test_never_live_rows_keep_the_seeds_embedding(trajectory):
    name, prog, ref, batches = trajectory
    end = prog["epochs"][-1]
    never = ~end["live"]
    touched = np.zeros(CAP, bool)
    for idx, _ in batches:
        touched[idx.reshape(-1)] = True
    # rows the batches touched in every epoch and that never went live
    assert (never & touched).sum() > 100
    assert np.array_equal(end["bits"][never], prog["init"]["bits"][never])
    # and live rows did move
    assert (end["bits"][end["live"]]
            != prog["init"]["bits"][end["live"]]).any()
    if name != "int8_rows":
        # float32 rows: the seed's table is the reference's, bit for bit
        V0 = np.ascontiguousarray(ref["V0"]).view(np.uint32)
        assert np.array_equal(prog["init"]["bits"][:, :V0.shape[1]], V0)


# ------------------------------------- (b) counts in epoch 0 and never after
def test_counts_are_pushed_in_epoch_0_only(trajectory):
    _, prog, _, batches = trajectory
    occ = np.zeros(CAP, np.float32)
    for idx, _ in batches:
        np.add.at(occ, idx.reshape(-1), 1.0)
    for e, p in enumerate(prog["epochs"]):
        assert np.array_equal(p["cnt"], occ), f"epoch {e}"
    assert getattr(prog["learner"], "_paired_dispatches", 0) > 0 \
        or prog["learner"].mesh is not None


# -------------------------------- (c) a live row whose w returns to 0
def _one_feature_batch(labels):
    """Rows that all hold one feature, position 0 of the step's slots: the
    program's own panel batch, chunked for the backward."""
    from difacto_tpu.data.rowblock import RowBlock
    from difacto_tpu.ops.batch import pad_panel, panel_chunk_tokens
    n = len(labels)
    blk = RowBlock(offset=np.arange(n + 1, dtype=np.int64),
                   label=np.asarray(labels, np.float32),
                   index=np.zeros(n, np.uint32), value=None)
    return panel_chunk_tokens(pad_panel(blk, num_uniq=1, batch_cap=16,
                                        width=1), 8)


@pytest.mark.parametrize("shrk", [True, False], ids=["l1_shrk", "no_shrk"])
def test_live_row_whose_w_returns_to_zero(shrk):
    """One feature on twelve rows a batch. Non-clicks drive its w off
    zero with its count over the threshold: live. Clicks then walk z
    back inside [-l1, l1]: w is exactly 0 again, ``live`` stays, and with
    ``l1_shrk`` the pull masks the embedding (V stands still through the
    steps at w = 0; without it AdaGrad keeps moving it). The reference
    follows the same batches."""
    from perfbench import reference as ref
    from difacto_tpu.losses import create
    from difacto_tpu.step import make_step_fns
    from difacto_tpu.store.local import pad_slots_oob
    from difacto_tpu.updaters.sgd_updater import (SGDUpdaterParam, col_V,
                                                  init_state, make_fns,
                                                  scal_cols)
    slot, k = 5, 4
    param = SGDUpdaterParam(V_dim=k, V_threshold=10, l1=1.0, lr=0.1,
                            l1_shrk=shrk, seed=3)
    h = ref.Hyper(V_dim=k, lr=0.1, l1=1.0, l1_shrk=shrk)
    state = init_state(param, 64)
    rs = ref.initial_state(jnp.asarray(col_V(param, state), jnp.float32))
    ridx = jnp.full((12, 1), slot, jnp.int32)
    slots = jnp.asarray(pad_slots_oob(np.array([slot], np.int32), 8, 64))
    fns = make_fns(param)
    train = jax.jit(make_step_fns(fns, create("fm", k))[1])

    def both(state, rs, labels, count):
        if count:
            state = fns.apply_count(state, slots, jnp.zeros(8).at[0].set(12.))
        state, _, _ = train(state, _one_feature_batch(labels), slots)
        rs, _ = ref.step(h, rs, ridx, jnp.asarray(labels, jnp.float32),
                         push_counts=count)
        w, _, _, cnt, live = (np.asarray(x)[slot]
                              for x in scal_cols(param, state))
        assert (bool(live), w != 0, cnt) == (
            bool(rs.live[slot]), bool(rs.w[slot] != 0), float(rs.cnt[slot]))
        np.testing.assert_allclose(w, float(rs.w[slot]), rtol=1e-5)
        return state, rs, w, bool(live), np.asarray(col_V(param, state))[slot]

    state, rs, w, live, V1 = both(state, rs, [0.0] * 12, True)
    assert w < 0 and live                   # over the count, w off zero
    # twelve clicks bring z to -1.67, seven clicks and five non-clicks to
    # -0.65: inside [-l1, l1]; six and six then leave it there
    state, rs, w, live, _ = both(state, rs, [1.0] * 12, False)
    assert w < 0 and live
    back = []
    for labels in ([1.0] * 7 + [0.0] * 5, [1.0] * 6 + [0.0] * 6,
                   [1.0] * 6 + [0.0] * 6):
        state, rs, w, live, V = both(state, rs, labels, False)
        assert w == 0 and live              # once live, always live
        back.append(V)
    # the first of the three pulled the row at w != 0 and updated V; the
    # two after it pulled it at w = 0: masked under l1_shrk, V stands
    # still; served without it, AdaGrad's l2 term keeps shrinking it
    assert np.array_equal(back[0], back[2]) is shrk
    assert np.array_equal(back[1], back[2]) is shrk
    state, rs, w, live, V = both(state, rs, [1.0] * 12, False)
    assert w > 0 and live                   # out the far side: served again
    assert not np.array_equal(V1, back[0])  # while w != 0 it had moved


@pytest.mark.parametrize("shrk", [True, False], ids=["l1_shrk", "no_shrk"])
def test_pull_mask_of_a_live_row_at_zero_w(shrk):
    """The pull's gate alone, on a row built by hand: live, w = 0."""
    from difacto_tpu.store.local import pad_slots_oob
    from difacto_tpu.updaters.sgd_updater import (SGDUpdaterParam,
                                                  build_rows, col_V,
                                                  init_state, make_fns,
                                                  scal_cols)
    param = SGDUpdaterParam(V_dim=4, V_threshold=10, l1_shrk=shrk, seed=3)
    state = init_state(param, 64)
    w, z, sg, cnt, live = (np.asarray(x).copy()
                           for x in scal_cols(param, state))
    cnt[[5, 6, 7]] = 12.0
    live[[5, 6]] = True         # 5: live at w = 0; 6: live with a weight
    w[[6, 7]] = 0.25            # 7: a weight, never live
    V = np.asarray(col_V(param, state), np.float32)
    state = state._replace(VVg=build_rows(
        param, 64, V, np.zeros_like(V), w, z, sg, cnt, live))
    slots = jnp.asarray(pad_slots_oob(np.array([5, 6, 7], np.int32), 8, 64))
    vmask = make_fns(param).get_rows(state, slots).v_mask
    assert np.asarray(vmask)[:3].tolist() == [0.0 if shrk else 1.0, 1.0, 0.0]


# ---------------------- (d) the pair program against two one-batch steps
_PAIR = """
import sys, threading
import numpy as np
sys.path.insert(0, %(root)r)
sys.path.insert(0, %(tests)r)
import test_activation_rule as T

def run(pairs):
    from perfbench import sut
    from difacto_tpu.learners import Learner
    kwargs = sut.learner_kwargs(T.CONFIG, T.TRAFFIC, %(data)r, 11)
    kwargs["max_num_epochs"] = 4
    ln = Learner.create("sgd")
    assert ln.init([(k, str(v)) for k, v in kwargs.items()]) == []
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
    cols = T._columns(ln)
    return (losses, np.asarray(ln.store.state.VVg).tobytes(),
            getattr(ln, "_paired_dispatches", 0), int(cols["live"].sum()),
            int((cols["w"] != 0).sum()))

paired, single = run(True), run(False)
assert paired[2] >= 8 and single[2] == 0, (paired[2], single[2])
assert 0 < paired[3] < paired[4], paired[3:]
assert paired[0] == single[0], (paired[0], single[0])
assert paired[1] == single[1]
print("byte-equal")
"""


def test_pair_program_equals_two_single_steps_with_the_gates_on(tmp_path):
    """Four epochs whose replays run in pairs (the count-free pair
    program, what a replay window times) against four whose replays run
    one batch a dispatch: the losses and the bytes of the whole fused
    table, live flags and counts in it. In a process of its own with the
    CPU held to SSE4.2 (tests/test_flat_table.py says why)."""
    data = str(tmp_path / "rows")
    _data(data, 11)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_cpu_max_isa=SSE4_2")
    out = subprocess.run(
        [sys.executable, "-c", _PAIR % {
            "root": ROOT, "tests": os.path.join(ROOT, "tests"),
            "data": data}],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("byte-equal")


# ------------------------------------------------------ (e) the gauge
def test_model_live_V_is_the_tables_own_column(trajectory, caplog):
    name, prog, _, _ = trajectory
    ln = prog["learner"]
    end = prog["epochs"][-1]
    live = int(end["live"][1:].sum())
    assert 0 < live == ln.obs.value("model_live_V", job="train")
    # nnz(w) of the epoch line charges V_dim for each of them
    nnz_w = int((end["w"][1:] != 0).sum())
    assert ln.obs.value("model_nnz_w", job="train") \
        == nnz_w + CONFIG["V_dim"] * live
    assert ln.store.evaluate_all()[2] == live
    assert ln.store.evaluate() == ln.store.evaluate_all()[:2]
    if name == "one_device":
        # and the line says it
        with caplog.at_level("INFO", logger="difacto_tpu"):
            seen = _run_program(ln.param.data_in, 11, epochs=1)
        said = [r.getMessage() for r in caplog.records
                if "live V = " in r.getMessage()]
        got = re.search(r"live V = (\d+), nnz\(w\) = (\S+),", said[-1])
        first = seen["epochs"][0]
        assert int(got.group(1)) == int(first["live"][1:].sum()) > 0
        assert float(got.group(2)) == pytest.approx(
            (first["w"][1:] != 0).sum() + CONFIG["V_dim"] * int(got.group(1)))
