"""ISSUE 37: the cell ``fm_v64_avazu.replay_avazu`` (the memory-adaptive
FM as the upstream ships it: ``V_threshold = 10``, ``l1_shrk = 1``,
``l1 = 1`` over Avazu's whole vocabulary, 2^24 fused bf16 rows on one
chip).

On the CPU at a tiny size: the cell with the gates on is ``correct``
through a whole run and the program's count of live rows is the
reference's; each gate fault planted in the program alone (the threshold
ignored, ``l1_shrk`` ignored, the activation never set) comes out not
correct through a whole run; the committed files are what the cell is
held to; and the step at the cell's real size and the row-cap rung its
traffic reaches, compiled for a described v5e, fits beside its batch
cache and scatters without the sorted flag.
"""

import json
import os

import pytest

import perfbench_tiny as tiny

CELL, CONFIG, TRAFFIC = ("fm_v64_avazu.replay_avazu", "fm_v64_avazu",
                         "replay_avazu")
# Avazu's 22 columns in small: the tiny ones as they are, the wide ones
# cut by a hundred or a thousand
TINY_TOKENS = [24, 7, 7, 47, 77, 26, 85, 56, 36, 2686, 6729, 83, 5, 4, 26,
               8, 9, 44, 4, 68, 17, 60]
SEED = 5
# the row-cap rung that the traffic's ~91k distinct rows a step reach
# (counted with the generator, PERF.md 4), and the panel's width
U, F = 98_304, 22


def _root(tmp_path):
    """The tiny cell: the committed configuration cut to 4096 float32
    rows of 8 factors and steps of 64 rows; the gates, l1 and every
    other key as committed."""
    root = tiny.make_root(str(tmp_path), config=CONFIG)
    path = os.path.join(root, "perfbench", "traffic", TRAFFIC + ".json")
    with open(path) as f:
        t = json.load(f)
    t["generator"]["cat_tokens"] = TINY_TOKENS
    with open(path, "w") as f:
        json.dump(t, f)
    return root


def _run(root, **kw):
    return tiny.run(root, workload=CELL, seed=SEED, seconds=0.2, **kw)


def _bad(res):
    return {n for n, c in res["checked"].items()
            if c["value"] == "inf" or c["value"] > c["limit"]}


# --------------------------------------------------------- the sound cell
def test_sound_cell_with_the_gates_on_is_correct(tmp_path):
    res, lines = _run(_root(tmp_path))
    assert res["correct"] is True, res["checked"]
    assert set(res["checked"]) == set(tiny.TINY_LIMITS)
    assert res["checked"]["keep_V"]["value"] == 0.0
    win = json.loads(lines["window"])
    assert win["paired_dispatches"] > 0 and win["epochs"] >= 1
    ref = json.loads(lines["reference"])
    prog, plain = ref["program"], ref["reference"]
    # the gates decide: after three steps some of the touched rows are
    # live, most are not, the same count on both sides; l1 = 1 has left
    # most touched weights at exactly 0
    assert 0 < prog["live"] == plain["live"] < prog["nnz_w"] \
        == plain["nnz_w"] < 0.5 * ref["touched_rows"]


# ------------------------------------------------------ the planted faults
@pytest.mark.parametrize("override, must_fail, sound", [
    # every touched row live from its first weight on: V of rows the
    # reference never updated has moved
    ({"V_threshold": 0}, {"keep_V", "grad_V", "change_V"}, {"loss1",
                                                           "grad_w"}),
    # a live embedding served at w = 0: three steps of 64 rows bring no
    # live row's weight back to 0, an epoch does, and the pair's numbers
    # (the reference's two gated steps from the program's own rows) see
    # it: which of them, hangs on the epoch whose first pair is watched
    ({"l1_shrk": 0}, set(),
     {"loss1", "loss2", "loss3", "grad_w", "grad_V", "change_w",
      "change_V", "keep_V", "round_V", "round_Vg"}),
    # no row ever live: no embedding moves
    ({"V_threshold": 10 ** 9}, {"grad_V", "change_V", "round_V",
                                "round_Vg"}, {"loss1", "grad_w", "keep_V"}),
], ids=["threshold_ignored", "l1_shrk_ignored", "never_live"])
def test_gate_fault_is_not_correct_through_a_whole_run(
        tmp_path, override, must_fail, sound):
    """``--override`` reaches the program's learner alone; the reference
    keeps the configuration's gates (``perfbench/calibrate.py
    --override`` reads the same faults on the chip)."""
    res, lines = _run(_root(tmp_path), override=override)
    assert json.loads(lines["window"])["paired_dispatches"] > 0
    assert res["correct"] is False
    bad = _bad(res)
    assert must_fail <= bad, res["checked"]
    assert not sound & bad, res["checked"]
    ref = json.loads(lines["reference"])
    live, want = ref["program"]["live"], ref["reference"]["live"]
    if override == {"V_threshold": 0}:
        # once live, always live: every row that ever had a weight
        assert live >= ref["program"]["nnz_w"] > 3 * want > 0
    elif override == {"l1_shrk": 0}:
        assert live == want > 0     # the same rows live, served otherwise
        assert bad and all(n.startswith("pair_") for n in bad), bad
    else:
        assert live == 0 < want


# ------------------------------------------------- the committed files
def _committed():
    from perfbench import run as R
    return R.load_cell(tiny.bench(), tiny.ROOT, CELL)


def test_committed_cell_is_the_sources_model():
    loaded = _committed()
    cell, cfg = loaded["cell"], loaded["config"]
    assert cell == dict(cell, config=CONFIG, traffic=TRAFFIC, chips=1)
    # the upstream's defaults, staged config 4's width
    assert (cfg["loss"], cfg["V_dim"], cfg["V_threshold"], cfg["l1_shrk"],
            cfg["l1"], cfg["l2"], cfg["V_l2"], cfg["V_lr"],
            cfg["V_init_scale"]) == ("fm", 64, 10, 1, 1, 0, 0.01, 0.01,
                                     0.01)
    from perfbench import reference
    h = reference.Hyper.of(cfg)
    d = reference.Hyper(V_dim=64)   # the reference's own defaults
    assert (h.V_threshold, h.l1_shrk, h.l1, h.l2, h.V_l2, h.V_lr) \
        == (d.V_threshold, d.l1_shrk, d.l1, d.l2, d.V_l2, d.V_lr) \
        == (10.0, True, 1.0, 0.0, 0.01, 0.01)
    assert cfg["hash_capacity"] == 2 ** 24 and cfg["batch_size"] == 65536
    assert (cfg["mesh_dp"], cfg["mesh_fs"]) == (1, 1)
    assert cfg["control"]["slot_dtype"] == "int8"
    entry = next(c for c in tiny.bench()["configs"] if c["name"] == CONFIG)
    kept = {"V_dim", "V_threshold", "l1_shrk", "l1", "l2", "V_l2", "V_lr",
            "V_init_scale"}
    assert not kept & set(entry["reduced"])
    assert kept <= set(cfg["about"]["kept"])
    # every changed key has its reason
    changed = dict(cfg["about"]["reduced"], **cfg["about"]["assumed"])
    assert set(entry["reduced"]) == set(changed) - {"why"}
    assert "9,449,445" in entry["source"] and "Avazu" in entry["source"]
    # the traffic: Avazu's 22 columns, whole
    t = loaded["traffic"]
    g = t["generator"]
    assert (g["int_fields"], g["cat_fields"], len(g["cat_tokens"])) \
        == (0, 22, 22)
    assert sum(g["cat_tokens"]) == 9_449_445
    assert (g["zipf_a"], g["ctr"]) == (1.1, 0.17)
    assert t["rows_per_epoch"] == 32 * 65536
    with open(os.path.join(tiny.ROOT, "perfbench", "traffic",
                           "replay.json")) as f:
        other = json.load(f)
    assert t["learner"] == other["learner"]
    assert list(t) == list(other) and list(g) == list(other["generator"])


def test_committed_limits_name_the_fused_numbers_and_no_other():
    from perfbench import check
    limits = {k: v for k, v in _committed()["limits"].items()
              if not k.startswith("_")}
    pair = ("pair_loss1", "pair_loss2", "pair_change_w", "pair_change_V",
            "pair_round_V")
    assert set(limits) == set(check.names(64)) | set(pair) | {"epoch_rows"}
    assert len(check.names(64)) == 10
    assert limits.pop("epoch_rows") == 0
    assert all(0 < v < 0.1 for v in limits.values()), limits
    # the limit admits the few rows on which |z| lands within the bf16
    # forward's rounding of l1 on one side only (at most 1.1e-3 in a
    # simulation of 280 seeds, PERF.md 2), and neither 8-bit rows
    # (4.3e-3 on the chip) nor a threshold that is ignored (1.5e-2)
    assert 1.1e-3 < limits["keep_V"] < 4e-3


def test_cell_is_declared_by_membership():
    b = tiny.bench()
    assert CONFIG in [c["name"] for c in b["configs"]]
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    rate = next(m for m in b["end_to_end"]
                if m["name"] == "replay_ex_per_s")
    assert CELL in rate["workloads"]
    for name in ("configs", "traffic", "limits"):
        stem = {"configs": CONFIG, "traffic": TRAFFIC, "limits": CELL}[name]
        assert os.path.exists(os.path.join(tiny.ROOT, "perfbench", name,
                                           stem + ".json"))
    # every per-layer metric that lists all six cells reads this one too
    from perfbench import run as R
    named = {m["name"] for m in R.metrics_of(b, "per_layer", CELL)}
    assert {"step_device_ms.replay", "leg_scatter_ms.replay",
            "step_roofline.replay"} <= named
    assert not {"collective_pct.replay", "exchange_roofline.replay"} & named


def test_committed_sizes():
    from difacto_tpu.ops.batch import row_cap
    from difacto_tpu.ops.fused import scatter_sweeps
    from difacto_tpu.updaters.sgd_updater import (SGDUpdaterParam,
                                                  state_bytes)
    from perfbench import work
    cfg = _committed()["config"]
    assert work.item_size(cfg) == 2
    param = SGDUpdaterParam(V_dim=64, V_dtype="bfloat16",
                            hash_capacity=cfg["hash_capacity"])
    assert state_bytes(param, 2 ** 24) == 512 * 2 ** 24 == 8_589_934_592
    assert 512 * 2 ** 24 > 0.5 * 16e9
    # ~91k distinct rows a step (counted with the generator, PERF.md 4)
    # pad to the rung 98,304, and a table of 2^24 rows is more than 40
    # rows an index: the scatter drops the sorted flag on one chip
    assert row_cap(90_800) == row_cap(91_400) == U
    assert not scatter_sweeps(2 ** 24, U)
    assert scatter_sweeps(2 ** 23, 294_912)     # the flagship sweeps


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


def test_step_at_2_24_rows_fits_beside_its_batch_cache(topo, monkeypatch):
    """``test_perfbench_compile.py``'s one-chip case at this cell's
    shapes: the train step at 2^24 rows, batch 65536 x 22, row cap
    98,304. Shapes only: nothing runs, and what it reads is the
    compiler's count, not a device number."""
    import re
    from jax.sharding import SingleDeviceSharding
    import test_perfbench_compile as C
    monkeypatch.setattr(C, "U", U)
    monkeypatch.setattr(C, "F", F)
    cfg = C._config(CONFIG)
    assert cfg["hash_capacity"] == 2 ** 24 and cfg["V_dim"] == 64
    compiled = C._compile_step(cfg, None,
                               SingleDeviceSharding(topo.devices[0]))
    total, m = C._per_device_bytes(compiled)
    table = 512 * 2 ** 24
    assert m.argument_size_in_bytes >= table    # 8.59 GB: over the floor
    assert table >= 0.5 * C.HBM
    # the donated table is updated in place, and the step leaves room
    # for the 4 GB batch cache of the replay traffic
    assert m.alias_size_in_bytes >= table
    assert total + 4096 * 2 ** 20 < C.HBM, (total, m)
    # one row gather declared sorted, one row scatter into the table
    # declared unique and NOT sorted: it pays by the index, not by the
    # table (ops/fused.scatter_sweeps)
    text = compiled.as_text()
    pulls = re.findall(rf"= bf16\[{U},256\]\S* gather\(.*"
                       r"indices_are_sorted=true", text)
    pushes = re.findall(r"= bf16\[16777216,256\]\S* scatter\(.*", text)
    assert len(pulls) == 1 and len(pushes) == 1, (pulls, pushes)
    assert "unique_indices=true" in pushes[0]
    assert "indices_are_sorted=true" not in pushes[0]
