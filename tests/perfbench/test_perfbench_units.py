"""The benchmark's own arithmetic: trace reduction on a recorded trace,
the necessary-work count, the peaks table, the generator, the judge."""

import gzip
import json
import os
import re

import numpy as np
import pytest

import perfbench_tiny as tiny  # noqa: F401  (puts the repo on sys.path)
from perfbench import calibrate, check, gen, layers, tracered, work
from perfbench import run as R

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(
            HERE, "data", "trace_rows_v64_replay.json.gz"), "rt") as f:
        d = json.load(f)
    return [tuple(r) for r in d["rows"]], d["span_ns"] * 1e-9


# ------------------------------------------------------------- the trace
def test_union_seconds_merges_overlaps():
    s, ms, me = tracered.union_seconds([0, 5, 20, 22], [10, 10, 5, 1])
    assert s == pytest.approx(20e-9)
    assert list(ms) == [0, 20] and list(me) == [15, 25]
    assert tracered.union_seconds([], [])[0] == 0.0


def test_recorded_trace_busy_and_idle(recorded):
    rows, span = recorded
    red = tracered.reduce(rows, span)
    assert red["devices"] == 1
    # three paired programs back to back: the chip is busy all but 40 us
    assert red["busy_s"] == pytest.approx(0.312863149, rel=1e-6)
    assert 0 < red["busy_s"] <= red["window_s"] == pytest.approx(span)
    ctx = {"trace": red, "steps": 6}
    assert layers.device_idle_pct(ctx) == pytest.approx(
        100 * (1 - 0.312863149 / span), rel=1e-6)
    assert layers.step_device_ms(ctx) == pytest.approx(52.1438, rel=1e-4)


def test_recorded_trace_step_count(recorded):
    rows, span = recorded
    red = tracered.reduce(rows, span)
    paired = [n for n in red["modules"] if "chunked2" in n]
    assert len(paired) == 1 and red["modules"][paired[0]] == 3


def test_recorded_trace_top_ops(recorded):
    rows, span = recorded
    red = tracered.reduce(rows, span)
    ops = red["device_ops"]
    assert len(ops) == 10
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    # the table scatter leads: the two halves of the pair
    assert ops[0][0].startswith("%fusion.8") and "8388608,256" in ops[0][0]
    assert sum(v for _, v in ops) <= red["busy_s"] * 1.0001


def test_collective_share_on_two_planes(recorded):
    rows, span = recorded
    # a second chip whose only work is two collectives, 30 ms together
    # (made by hand: the recorded trace is of one chip)
    extra = [("/device:TPU:1", "XLA Ops", "all-gather-start.3", 1000,
              10_000_000),
             ("/device:TPU:1", "XLA Ops", "%all-reduce.7 = f32[8]", 50_000_000,
              20_000_000),
             ("/device:TPU:1", "XLA Ops", "%fusion.1 = f32[8]", 90_000_000,
              5_000_000)]
    red = tracered.reduce(list(rows) + extra, span)
    assert red["devices"] == 2
    assert red["collective_s_fullest"] == pytest.approx(0.030)
    assert red["busy_s"] == pytest.approx((0.312863149 + 0.035) / 2,
                                          rel=1e-6)
    # one chip has no collective time
    assert tracered.reduce(rows, span)["collective_s_fullest"] == 0.0


@pytest.mark.parametrize("name, is_collective", [
    ("%all-reduce.7 = f32[8]{0} all-reduce(f32[8]{0} %p), to_apply=%add",
     True),
    ("all-gather-start.3", True),
    # jax.lax.psum inside a shard_map: XLA names the instruction after
    # the primitive, the opcode behind the "=" is the collective's
    ("%psum_invariant.7 = u32[9437184,4]{1,0} all-reduce(u32[9437184,4]"
     "{1,0} %fusion.3), channel_id=1, to_apply=%region_1.5", True),
    ("%ppermute.2 = (f32[8]{0}, f32[8]{0}) collective-permute-start("
     "f32[8]{0} %x), source_target_pairs={{0,1}}", True),
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %all-reduce.7), kind=kLoop",
     False),
    ("%all_reduce_like.1 = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)",
     False),
    ("%fusion.8", False),
], ids=["by_name", "bare_name", "psum_invariant", "ppermute", "consumer",
        "lookalike", "no_text"])
def test_collective_is_found_by_name_or_opcode(name, is_collective):
    assert tracered.is_collective(name) is is_collective
    rows = [("/device:TPU:0", "XLA Ops", name, 0, 4_000_000),
            ("/device:TPU:0", "XLA Ops", "%fusion.9 = f32[8]{0} fusion()",
             4_000_000, 6_000_000)]
    red = tracered.reduce(rows, 0.010)
    assert red["collective_s_fullest"] == pytest.approx(
        0.004 if is_collective else 0.0)


def test_device_events_are_clipped_to_the_windows_marks():
    rows = [("/device:TPU:0", "XLA Ops", "%before", 0, 2_000_000),
            ("/device:TPU:0", "XLA Ops", "%across", 9_000_000, 2_000_000),
            ("/device:TPU:0", "XLA Ops", "%inside", 12_000_000, 3_000_000),
            ("/device:TPU:0", "XLA Ops", "%after", 19_000_000, 4_000_000),
            ("/host:CPU", "main/1", tracered.MARKS[0], 10_000_000, 200_000),
            ("/host:CPU", "main/1", tracered.MARKS[1], 20_000_000, 200_000)]
    red = tracered.reduce(rows, 0.010)
    assert red["clipped"] is True
    # 1 ms of %across, %inside, 1 ms of %after: nothing outside counts
    assert red["busy_s"] == pytest.approx(0.005)
    assert red["busy_s_unclipped"] == pytest.approx(0.011)
    assert dict(map(tuple, red["device_ops"]))["%after"] == \
        pytest.approx(0.001)
    assert "%before" not in dict(map(tuple, red["device_ops"]))
    # without the marks (an older trace) nothing is cut
    red = tracered.reduce(rows[:4], 0.010)
    assert red["clipped"] is False and red["busy_s"] == pytest.approx(0.011)


def test_idle_gaps_named_by_host_event():
    rows = [("/device:TPU:0", "XLA Ops", "%a", 0, 1_000_000),
            ("/device:TPU:0", "XLA Ops", "%b", 5_000_000, 1_000_000),
            ("/device:TPU:0", "XLA Ops", "%c", 6_020_000, 1_000_000),
            ("/device:TPU:0", "XLA Ops", "%d", 9_000_000, 1_000_000),
            ("/host:CPU", "main/1", "pack", 900_000, 4_000_000)]
    red = tracered.reduce(rows, 0.010)
    assert red["busy_s"] == pytest.approx(0.004)
    gaps = dict(map(tuple, red["idle_gaps"]))
    assert gaps["pack"] == pytest.approx(0.004)
    assert gaps["no host event of 0.1 ms"] == pytest.approx(0.00198)


def test_readers_return_nothing_without_a_trace():
    ctx = {"res": {"stages": {}, "window_rows": 0}, "trace": None,
           "steps": 0, "least": None}
    for f in (layers.step_device_ms, layers.step_roofline, layers.step_mfu,
              layers.device_idle_pct):
        assert f(ctx) is None


def test_roofline_and_mfu_from_least_time():
    ctx = {"trace": {"busy_s": 2.0, "window_s": 4.0}, "steps": 100,
           "least": {"seconds": 0.0002}}
    assert layers.step_roofline(ctx) == pytest.approx(1.0)   # 0.2/20 ms
    assert layers.step_mfu(ctx) == pytest.approx(0.5)        # 0.2/40 ms
    assert layers.step_mfu(ctx) < layers.step_roofline(ctx)


# -------------------------------------------------------- necessary work
def test_step_work_hand_counted():
    # 3 rows of 2 features, 5 distinct, V_dim 4 in 2-byte items
    w = work.step_work(u=5, rows=3, nnz=6, V_dim=4, itemsize=2)
    row = 2 * 4 * 2 + 4 * 4                 # V, Vg and four f32 scalars
    assert w["bytes"] == 2 * 5 * row + 6 * 4 + 3 * 4
    assert w["flops"] == 4 * 6 * 4 + 4 * 3 * 4 + 11 * 5 * 4 + 16 * 5 + 12
    valued = work.step_work(5, 3, 6, 4, 2, valued=True)
    assert valued["bytes"] - w["bytes"] == 6 * 4


def test_step_work_ignores_the_stored_width():
    """A V16 float32 row is stored 128 lanes wide or compact; a V64 bf16
    row 256 lanes: the count takes V_dim and the item size alone."""
    a = work.step_work(1000, 64, 64 * 39, 16, 4)
    assert a["bytes"] == 2 * 1000 * (2 * 16 * 4 + 16) + 64 * 39 * 4 + 64 * 4
    # equal item size, equal work, whatever the layout pads to
    assert work.step_work(1000, 64, 64 * 39, 16, 4) == a
    b16 = work.step_work(1000, 64, 64 * 39, 32, 2)
    assert b16["bytes"] == a["bytes"]        # 32 bf16 items = 16 f32


@pytest.mark.parametrize("config, size", [
    ({}, 4),
    ({"V_dtype": "float32"}, 4),
    ({"V_dtype": "bfloat16"}, 2),
    ({"V_dtype": "float32", "slot_dtype": "bf16"}, 2),
    ({"V_dtype": "bfloat16", "slot_dtype": "int8"}, 1),
    ({"V_dtype": "float32", "slot_dtype": "fp8"}, 1),
    ({"V_dtype": "bfloat16", "slot_dtype": "fp32"}, 2),
], ids=["default", "f32", "bf16", "slot_bf16", "int8", "fp8", "slot_fp32"])
def test_item_size_is_the_stored_types(config, size):
    assert work.item_size(config) == size
    # the exchange's work takes the same size: an 8-bit row is not
    # counted at four bytes an item
    from perfbench import exchange
    peaks = work.load_peaks("TPU v5 lite")
    u, rows, width = 1000.0, 64, 39
    least = work.least_seconds(
        work.step_work(u, rows, rows * width, 64, size), peaks, 4)
    t = exchange.least_exchange_seconds(
        least, peaks, 200e9, 4, rows, width,
        dict(config, V_dim=64, mesh_fs=4))
    assert t == pytest.approx(u * 0.75 * (2 * 64 * size + 16) / 200e9)


def test_step_work_of_the_flat_table():
    """V_dim = 0: four float32 scalars a row each way, the indices and
    the labels; the item size has nothing to multiply."""
    w = work.step_work(u=279_000, rows=65536, nnz=65536 * 39, V_dim=0,
                       itemsize=4)
    assert w["bytes"] == 2 * 279_000 * 16 + 65536 * 39 * 4 + 65536 * 4
    assert w["bytes"] == 19_413_760            # about 20 MB a step
    assert w["flops"] == 16 * 279_000 + 2 * 65536 * 39
    assert work.step_work(279_000, 65536, 65536 * 39, 0, 1) == w
    t = work.least_seconds(w, work.load_peaks("TPU v5 lite"))
    assert t["bound"] == "hbm" and t["seconds"] == pytest.approx(23.7e-6,
                                                                 rel=1e-2)


def test_peaks_table():
    p = work.load_peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.load_peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.load_peaks("_source")


def test_least_seconds_names_its_bound():
    p = work.load_peaks("TPU v5 lite")
    w = work.step_work(284_000, 65536, 65536 * 39, 64, 2)
    t = work.least_seconds(w, p)
    assert t["bound"] == "hbm" and t["seconds"] == t["hbm_seconds"]
    assert t["seconds"] == pytest.approx(w["bytes"] / 819e9)
    assert work.least_seconds(w, p, chips=4)["seconds"] == \
        pytest.approx(t["seconds"] / 4)


# ---------------------------------------------------------- the generator
SPEC = gen.Spec(int_tokens=20, cat_tokens=300)


def test_generator_same_seed_same_rows():
    a = gen.make_member(7, 3, 64, gen.make_tables(7, SPEC))
    b = gen.make_member(7, 3, 64, gen.make_tables(7, SPEC))
    assert all((x == y).all() for x, y in zip(a, b))
    c = gen.make_member(8, 3, 64, gen.make_tables(8, SPEC))
    assert not (a[1] == c[1]).all()


def test_generator_same_seed_same_bytes(tmp_path):
    out = []
    for d in ("a", "b"):
        root = tiny.make_root(str(tmp_path / d))
        data_dir = str(tmp_path / d / "data.rec")
        os.makedirs(data_dir)
        loaded = R.load_cell(tiny.bench(), root, "fm_v64_criteo.replay")
        data = R.make_data(2**31 + 11, loaded["config"], loaded["traffic"],
                           data_dir, 3)
        names = sorted(os.listdir(data_dir))
        assert len(names) == data["n_members"] == 8
        out.append([open(os.path.join(data_dir, n), "rb").read()
                    for n in names])
    assert out[0] == out[1]


def test_every_member_is_whole_batches(tmp_path):
    from difacto_tpu.data.rec import read_rec_block_ex
    root = tiny.make_root(str(tmp_path))
    data_dir = str(tmp_path / "data.rec")
    os.makedirs(data_dir)
    loaded = R.load_cell(tiny.bench(), root, "fm_v64_criteo.replay")
    R.make_data(3, loaded["config"], loaded["traffic"], data_dir, 3)
    for n in os.listdir(data_dir):
        blk, uniq = read_rec_block_ex(os.path.join(data_dir, n))
        assert blk.size == 64 and blk.nnz == 64 * 39
        assert uniq is not None and (np.diff(uniq.astype(np.float64)) > 0
                                     ).all()
    loaded["traffic"]["rows_per_epoch"] = 100
    with pytest.raises(ValueError):
        R.make_data(3, loaded["config"], loaded["traffic"], data_dir, 3)


@pytest.mark.parametrize("seed", [1, 2, 3, 2**31 + 5])
def test_generator_click_rate_is_the_traffics(seed):
    t = gen.make_tables(seed, SPEC)
    label, _ = gen.make_member(seed, 0, 20000, t)
    assert abs(label.mean() - SPEC.ctr) < 0.02


def test_generator_takes_a_size_for_each_field():
    sizes = [3, 5000, 40, 7] + [11] * 22
    spec = gen.Spec(int_tokens=20, cat_tokens=sizes, zipf_a=1.1)
    assert spec.n_features == 13 * 20 + sum(sizes)
    t = gen.make_tables(9, spec)
    assert len(t.rev_sorted) == spec.n_features
    assert (t.rev_sorted[1:] > t.rev_sorted[:-1]).all()    # none repeats
    _, g = gen.make_member(9, 0, 4000, t)
    tok = g - t.base[None, :]
    assert (tok >= 0).all() and (tok.max(0) < np.asarray(spec.sizes)).all()
    assert len(np.unique(tok[:, 13])) == 3                 # every token
    assert len(np.unique(tok[:, 14])) > 500                # a long tail
    with pytest.raises(ValueError):
        gen.Spec(cat_tokens=[1, 2, 3]).sizes


def test_localize_is_a_sorted_unique():
    t = gen.make_tables(4, SPEC)
    _, g = gen.make_member(4, 0, 64, t)
    uniq, index = gen.localize(g, t)
    rev = t.rev_of(g.reshape(-1))
    want, inv = np.unique(rev, return_inverse=True)
    assert (uniq == want).all() and (index == inv).all()


def test_slots_follow_the_hashed_store():
    from difacto_tpu.store.local import hash_slots
    t = gen.make_tables(4, SPEC)
    rev = t.rev_sorted[:5000]
    for cap in (4096, 8388608):
        assert (gen.slots_of(rev, cap) == hash_slots(rev, cap)).all()


def test_field_rides_the_reversed_ids_top():
    from difacto_tpu.base import reverse_bytes
    t = gen.make_tables(4, SPEC)
    g = np.array([0, 25, 20 * 13 + 7], np.int32)      # fields 0, 1, 13
    ids = reverse_bytes(t.rev_of(g))
    assert list(ids & np.uint64(0xFFF)) == [0, 1, 13]


# -------------------------------------------------------------- the judge
@pytest.mark.parametrize("V_dim", [8, 0], ids=["fused", "flat"])
def test_judge_holds_every_number_to_its_limit(V_dim):
    names = check.names(V_dim)
    leaf = "grad_V" if V_dim else "zero_w"
    nums = {n: 1e-6 for n in names}
    lim = dict({n: 1e-5 for n in names}, _about="text")
    ok, checked = check.judge(nums, lim, names)
    assert ok and list(checked) == list(names)
    # a number the run produced and no limit holds, and a limit whose
    # number the run did not produce (the pair never ran), both fail
    assert not check.judge(dict(nums, pair_loss1=0.0), lim, names)[0]
    ok, checked = check.judge(nums, dict(lim, pair_loss1=1e-5), names)
    assert not ok and checked["pair_loss1"]["value"] == "inf"
    assert check.judge(dict(nums, pair_loss1=0.0),
                       dict(lim, pair_loss1=1e-5), names)[0]
    assert checked["loss2"] == {"value": 1e-6, "limit": 1e-5}
    assert not check.judge(dict(nums, **{leaf: 2e-5}), lim, names)[0]
    assert not check.judge(dict(nums, loss3=float("nan")), lim, names)[0]
    assert not check.judge(nums, {k: v for k, v in lim.items()
                                  if k != "change_w"}, names)[0]
    # a number of the layout that the run lost and the file forgot
    assert not check.judge({k: v for k, v in nums.items() if k != leaf},
                           {k: v for k, v in lim.items() if k != leaf},
                           names)[0]


def test_each_layout_has_its_numbers():
    assert check.names(64) == check.NUMBERS and len(check.NUMBERS) == 10
    flat = check.names(0)
    assert flat == ("loss1", "loss2", "loss3", "grad_w", "change_w",
                    "round_w", "round_z", "round_sg", "zero_w")
    assert not any(n.endswith(("_V", "_Vg")) for n in flat)
    # the other layout's limits judge no run of this one
    nums = {n: 0.0 for n in flat}
    assert not check.judge(nums, {n: 1.0 for n in check.NUMBERS}, flat)[0]
    assert not check.judge(nums, {n: 1.0 for n in flat},
                           check.NUMBERS)[0]


def test_numbers_take_no_gap_of_an_empty_leaf():
    """The flat table's probe reports a V of no columns, norm 0: the
    leaves compared are the reference's."""
    prog = {"loss": [2.0, 1.0, 1.0], "grad": {"w": 3.0, "V": 0.0},
            "change": {"w": 1.0, "V": 0.0}, "rows": "p"}
    ref = {"loss": [2.0, 1.0, 1.0], "grad": {"w": 3.0},
           "change": {"w": 1.0}, "rows": "r"}
    nums = check.numbers(prog, ref, lambda p, r: {"zero_w": 0.0,
                                                  "got": float(p + r == "pr")})
    assert nums == {"loss1": 0.0, "loss2": 0.0, "loss3": 0.0,
                    "grad_w": 0.0, "change_w": 0.0, "zero_w": 0.0,
                    "got": 1.0}


def test_epoch_rows_is_exact():
    assert check.epoch_rows([512.0, 512.0], 512) == 0.0
    assert check.epoch_rows([512.0, 448.0], 512) == 0.125   # a batch short
    assert check.epoch_rows([], 512) == float("inf")
    assert check.judge(dict({n: 0.0 for n in check.NUMBERS},
                            epoch_rows=0.0),
                       dict({n: 0.0 for n in check.NUMBERS},
                            epoch_rows=0), check.NUMBERS)[0]


def test_gap_is_of_norms():
    assert check.gap(1.01, 1.0) == pytest.approx(0.01)
    assert check.gap(0.0, 2.0) == 1.0          # a leaf that did not move
    assert check.gap(4.0, 2.0) == 1.0          # or moved double
    assert check.gap(1.0, 0.0) == float("inf")


# ------------------------------------------------------- BENCHMARK.json
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_is_served_by_files():
    b = tiny.bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    bdir = os.path.join(tiny.ROOT, b["paths"][0])
    layout = {}
    for c in b["configs"]:
        assert NAME.match(c["name"])
        with open(os.path.join(tiny.ROOT, c["file"])) as f:
            cfg = json.load(f)
        # compared with its plain reference: the file names it, and it
        # lies in the benchmark's own directory
        ref = os.path.join(tiny.ROOT, cfg["reference"])
        assert os.path.exists(ref) and cfg["reference"].startswith(
            b["paths"][0] + "/")
        assert set(c["reduced"]) <= set(cfg)
        assert set(c["reduced"]) == set(cfg["about"]["reduced"]) | {
            k for k in cfg["about"]["assumed"] if k != "why"}
        # the control: the program's keys for its nearest precision
        # below, or one of calibrate.py's planted faults by name
        control = dict(cfg["control"])
        assert control.pop("why") and cfg["precision"]
        if "fault" in control:
            assert control == {"fault": control["fault"]}
            assert control["fault"] in calibrate.FAULTS
        else:
            assert control and set(control) <= {"V_dtype", "slot_dtype"}
        layout[c["name"]] = int(cfg["V_dim"])
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            bdir, "traffic", w["traffic"] + ".json"))
        # the limits file names the numbers of its own layout, and none
        # of the other's
        with open(os.path.join(bdir, "limits", w["name"] + ".json")) as f:
            limited = {k for k in json.load(f) if not k.startswith("_")}
        mine = set(check.names(layout[w["config"]]))
        other = set(check.NUMBERS + check.FLAT_NUMBERS) - mine
        assert limited >= mine | {"epoch_rows"} and not limited & other
        mine = [m["name"] for m in R.metrics_of(b, "end_to_end",
                                                w["name"])]
        assert "setup_s" in mine and len(mine) >= 2
        assert R.metrics_of(b, "per_layer", w["name"])
    for m in b["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert R.load_reader(bdir, m["name"]) is not None
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_metrics_of_selects_by_cell():
    b = {"end_to_end": [{"name": "a", "workloads": ["x"]},
                        {"name": "b", "workloads": ["y"]},
                        {"name": "setup_s"}],
         "per_layer": [{"name": "pa", "moves": "a"},
                       {"name": "pb", "moves": "b"},
                       {"name": "ps", "moves": "setup_s"},
                       {"name": "only_y", "moves": "setup_s",
                        "workloads": ["y"]}]}
    assert [m["name"] for m in R.metrics_of(b, "end_to_end", "x")] == \
        ["a", "setup_s"]
    assert [m["name"] for m in R.metrics_of(b, "per_layer", "x")] == \
        ["pa", "ps"]
    assert [m["name"] for m in R.metrics_of(b, "per_layer", "y")] == \
        ["pb", "ps", "only_y"]
