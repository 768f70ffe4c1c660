"""ISSUE 35: the cell ``lr_l1_criteo.replay`` (the upstream's cluster
deployment: l1 logistic regression, ``V_dim = 0``, ``l1 = 4``, a flat
table of 2^29 rows on one chip).

On the CPU at a tiny size: an ``l1`` that is a multiple of 0.5 makes step
1's exact ties (every p is 0.5 before it, so z is a multiple of 0.5 and
|z| = l1 is frequent) and the program settles them as the reference
does; each planted fault comes out not correct through a whole run; the
committed files are what the cell is held to; and the step at the cell's
real size, compiled for a described v5e, fits beside its batch cache.
"""

import json
import os

import numpy as np
import pytest

import perfbench_tiny as tiny

CELL, CONFIG = "lr_l1_criteo.replay", "lr_l1_criteo"
# the tiny cell: the configuration's own file cut to 4096 rows and steps
# of 64 rows; l1 = 1 = two tokens' gradients at step 1 (the cell's 4 is
# eight tokens', more than a 64-row batch gives most rows)
TIE_L1 = 1.0


def _root(tmp_path):
    return tiny.make_root(str(tmp_path), config=CONFIG, V_dim=0,
                          l1=TIE_L1, limits=tiny.FLAT_LIMITS)


def _run(root, **kw):
    return tiny.run(root, workload=CELL, **kw)


# ------------------------------------------------------------ the ties
def _three_steps(root, seed):
    """(program rows, reference rows) after the first three steps, as
    ``calibrate.reading`` reads them, with the rows kept."""
    from perfbench import run as R, sut
    loaded = R.load_cell(tiny.bench(), root, CELL)
    config, traffic = loaded["config"], loaded["traffic"]
    ref_mod = R.load_reference(root, config)
    cfg_kw = {k: v for k, v in config.items() if k not in R.META}
    hyper = ref_mod.Hyper.of(cfg_kw)
    work = os.path.join(root, "rows")
    os.makedirs(work)
    data = R.make_data(seed, config, traffic, work, sut.N_STEPS)
    rows, batches = R.first_steps(data, config, ref_mod)
    kwargs = sut.learner_kwargs(cfg_kw, traffic, work, seed)
    prog = sut.drive(kwargs, rows, 0.0, stop_after="first")["probe"]
    V0 = ref_mod.initial_V(kwargs["seed"], int(config["hash_capacity"]),
                           rows, hyper)
    ref = ref_mod.follow(hyper, V0, batches)
    return prog["rows"], ref["rows"], ref_mod.rel_diff


def test_exact_ties_fall_alike_and_zero_w_is_not_vacuous(tmp_path):
    prog, ref, rel_diff = _three_steps(_root(tmp_path), seed=5)
    assert rel_diff(prog, ref)["zero_w"] == 0.0
    upd = np.asarray(ref["sg"]) != 0
    assert upd.sum() > 2000
    for side in (prog, ref):
        w, z = np.asarray(side["w"])[upd], np.asarray(side["z"])[upd]
        # the number compares something: about four touched weights in
        # five are exactly 0 and one in five is not, on both sides
        share = float((w == 0).mean())
        assert 0.7 < share < 0.85, share
        # rows that only step 1 touched hold a z that is a multiple of
        # 0.5; those at |z| = l1 exactly are zeroed (<=), none kept
        tie = np.abs(z) == TIE_L1
        assert tie.sum() >= 20 and not w[tie].any()
        # and just past the tie a weight lives
        assert w[np.abs(z) == TIE_L1 + 0.5].all()
    assert np.array_equal(np.asarray(prog["w"]) == 0,
                          np.asarray(ref["w"]) == 0)


@pytest.mark.parametrize("tokens, lives", [(8, False), (9, True)],
                         ids=["z_equals_l1", "z_past_l1"])
def test_a_row_on_the_threshold(tokens, lives):
    """One row built to land on |z| = l1 = 4 exactly: eight tokens of
    non-clicks at p = 0.5 are a gradient of 4.0 with no rounding. The
    program's ``ftrl_w`` and the reference's step leave its weight at 0;
    a ninth token moves it."""
    import jax.numpy as jnp
    from difacto_tpu.updaters.sgd_updater import ftrl_w
    from perfbench import reference as ref
    h = ref.Hyper(V_dim=0, lr=0.1, l1=4.0, l2=0.02)
    s0 = ref.initial_state(jnp.zeros((4, 0), jnp.float32))
    idx = jnp.full((tokens, 1), 2, jnp.int32)
    s1, _ = ref.step(h, s0, idx, jnp.zeros((tokens,), jnp.float32))
    assert float(s1.z[2]) == -0.5 * tokens
    assert float(s1.sg[2]) == 0.5 * tokens
    assert bool(s1.w[2] != 0) is lives
    zero = jnp.zeros((1,), jnp.float32)
    w, z, sg = ftrl_w(zero, zero, zero, jnp.full((1,), 0.5 * tokens),
                      4.0, 0.02, 0.1, 1.0)
    assert float(z[0]) == float(s1.z[2]) and float(sg[0]) == float(s1.sg[2])
    assert bool(w[0] != 0) is lives
    assert float(w[0]) == float(s1.w[2])


# -------------------------------------------------- the planted faults
def _no_l1(monkeypatch):
    return {"override": {"l1": 0}}


def _half_batch(monkeypatch):
    """Every train step the learner builds sees every second row of its
    batch masked out (``test_perfbench_harness._broken_step``'s seam)."""
    import test_perfbench_harness as H

    def breaker(train):
        def step(state, batch, slots):
            import jax.numpy as jnp
            keep = (jnp.arange(batch.row_mask.shape[0]) % 2).astype(
                batch.row_mask.dtype)
            return train(state, batch._replace(
                row_mask=batch.row_mask * keep), slots)
        return step

    H._broken_step(monkeypatch, breaker)
    return {}


def _stale_pair(monkeypatch):
    """The pair-replay program alone, broken as ``calibrate.py``'s
    ``stale`` breaks the reference: its second step reads the rows from
    before the first, and its rows overwrite the first step's."""
    from difacto_tpu.utils import jaxtrace
    real, single = jaxtrace.jit, {}

    def jit(fn, *a, **kw):
        name = getattr(fn, "__name__", "")
        if name == "packed_panel_train_chunked":
            single["fn"] = fn
        if name == "packed_panel_train_chunked2":
            one = single["fn"]

            def broken(state, pa, pb, *statics):
                import jax.numpy as jnp
                s1, o1, a1 = one(state, *pa, *statics)
                sb, o2, a2 = one(state, *pb, *statics)
                hit = sb.sqrt_g != state.sqrt_g
                return s1._replace(
                    w=jnp.where(hit, sb.w, s1.w),
                    z=jnp.where(hit, sb.z, s1.z),
                    sqrt_g=jnp.where(hit, sb.sqrt_g, s1.sqrt_g)), \
                    o1, a1, o2, a2
            fn = broken
        return real(fn, *a, **kw)

    monkeypatch.setattr(jaxtrace, "jit", jit)
    return {}


@pytest.mark.parametrize("plant, must_fail, sound", [
    (_no_l1, {"zero_w", "round_w", "change_w"}, {"loss1", "grad_w"}),
    (_half_batch, {"loss1", "loss2", "loss3", "grad_w", "change_w",
                   "round_w", "round_z", "round_sg", "zero_w"}, set()),
    (_stale_pair, {"pair_change_w", "pair_round_w"},
     {"pair_loss1", "loss1", "zero_w", "round_w"}),
], ids=["no_l1", "half_batch", "stale_pair"])
def test_planted_fault_is_not_correct_through_a_whole_run(
        tmp_path, monkeypatch, plant, must_fail, sound):
    root = _root(tmp_path)
    res, lines = _run(root, seconds=0.2, **plant(monkeypatch))
    assert json.loads(lines["window"])["paired_dispatches"] > 0
    assert res["correct"] is False
    bad = {n for n, c in res["checked"].items() if c["value"] > c["limit"]}
    assert must_fail <= bad, res["checked"]
    assert not sound & bad, res["checked"]
    if plant is _no_l1:
        # every touched weight non-zero where four in five are 0: the
        # number that carries the control at the source's l1
        assert res["checked"]["zero_w"]["value"] > 0.7
        ref = json.loads(lines["reference"])
        assert ref["program"]["nnz_w"] > 4 * ref["reference"]["nnz_w"] > 0


def test_sound_run_at_the_tie_l1_is_correct(tmp_path):
    res, lines = _run(_root(tmp_path), seconds=0.2)
    assert res["correct"] is True, res["checked"]
    assert set(res["checked"]) == set(tiny.FLAT_LIMITS)
    assert res["checked"]["zero_w"]["value"] == 0.0
    assert json.loads(lines["window"])["table_bytes"] == 4096 * 17


# ------------------------------------------------- the committed files
def _committed():
    from perfbench import run as R
    return R.load_cell(tiny.bench(), tiny.ROOT, CELL)


def test_committed_cell_is_the_sources_model():
    loaded = _committed()
    cell, cfg = loaded["cell"], loaded["config"]
    assert cell == dict(cell, config=CONFIG, traffic="replay", chips=1)
    # the source's own: the model, its regulariser
    assert (cfg["loss"], cfg["V_dim"], cfg["l1"], cfg["l2"]) \
        == ("fm", 0, 4, 0.02)
    entry = next(c for c in tiny.bench()["configs"] if c["name"] == CONFIG)
    assert not {"V_dim", "l1", "l2", "loss"} & set(entry["reduced"])
    assert "V_dim=0" in entry["source"] and "l1=4" in entry["source"]
    assert cfg["control"]["fault"] == "no_l1"
    assert cfg["hash_capacity"] == 2 ** 29 and cfg["batch_size"] == 65536
    assert (cfg["mesh_dp"], cfg["mesh_fs"]) == (1, 1)
    # every changed key has its reason
    changed = dict(cfg["about"]["reduced"], **cfg["about"]["assumed"])
    assert set(entry["reduced"]) == set(changed) - {"why"}
    assert "50" in cfg["about"]["deployment"]
    # the traffic file is the other replay cells', whole
    assert loaded["traffic"]["rows_per_epoch"] == 32 * 65536


def test_committed_limits_name_the_flat_numbers_and_no_other():
    from perfbench import check
    limits = {k: v for k, v in _committed()["limits"].items()
              if not k.startswith("_")}
    pair = ("pair_loss1", "pair_loss2", "pair_change_w", "pair_round_w")
    assert set(limits) == set(check.names(0)) | set(pair) | {"epoch_rows"}
    assert limits.pop("epoch_rows") == 0
    assert all(0 < v < 0.1 for v in limits.values()), limits
    # zero_w is a count of rows: its limit admits a few rows within
    # rounding of l1 among ~640k and nothing like a missing threshold
    assert limits["zero_w"] <= 1e-4


def test_committed_sizes():
    from difacto_tpu.updaters.sgd_updater import (SGDUpdaterParam,
                                                  state_bytes)
    from perfbench import work
    cfg = _committed()["config"]
    assert work.item_size(cfg) == 4
    param = SGDUpdaterParam(V_dim=cfg["V_dim"],
                            hash_capacity=cfg["hash_capacity"])
    assert state_bytes(param, cfg["hash_capacity"]) == 17 * 2 ** 29
    assert state_bytes(param, cfg["hash_capacity"]) > 0.5 * 16e9


# --------------------- the real size, compiled for a described v5e chip
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


def test_flat_step_fits_beside_its_batch_cache(topo):
    """``test_perfbench_compile.py``'s one-chip case for the flat table:
    the train step at 2^29 rows, batch 65536 x 39, row cap 294,912.
    Shapes only: nothing runs, and what it reads is the compiler's count,
    not a device number."""
    from jax.sharding import SingleDeviceSharding
    import test_perfbench_compile as C
    cfg = C._config(CONFIG)
    assert cfg["hash_capacity"] == 2 ** 29 and cfg["V_dim"] == 0
    compiled = C._compile_step(cfg, None,
                               SingleDeviceSharding(topo.devices[0]))
    total, m = C._per_device_bytes(compiled)
    table = 17 * 2 ** 29
    assert m.argument_size_in_bytes >= table     # 9.13 GB: over the floor
    assert table >= 0.25 * C.HBM
    # the three leaves the push writes are updated in place (w, z,
    # sqrt_g: 12 of the 17 bytes a row); cnt and v_live pass through
    assert m.alias_size_in_bytes >= 12 * 2 ** 29
    assert total + 4096 * 2 ** 20 < C.HBM, (total, m)
    # the flat path's table operations as the TPU's compiler leaves
    # them: THREE scalar gathers of the row cap (the step's text reads w
    # twice, in get_rows and again in apply_grad; the two are merged)
    # and three scatters into the table, declared sorted and unique:
    # what store_gather_bytes_total counts (updaters.gather_bytes)
    import re
    text = compiled.as_text()
    pulls = re.findall(rf"= f32\[{C.U}\]\S* gather\(.*"
                       r"indices_are_sorted=true", text)
    pushes = re.findall(r"= f32\[536870912\]\S* scatter\(.*"
                        r"indices_are_sorted=true, unique_indices=true",
                        text)
    assert (len(pulls), len(pushes)) == (3, 3), (pulls, pushes)
