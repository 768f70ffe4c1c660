"""The plain reference against the reference suite's golden constants
(tests/test_losses.py: fm_loss_test.cc) and against itself in a lower
precision."""

import jax.numpy as jnp
import numpy as np
import pytest

import perfbench_tiny as tiny  # noqa: F401
from perfbench import reference as ref


@pytest.fixture(scope="module")
def rcv1_batch(rcv1_path):
    """The first 100 rcv1 rows as a padded panel: (idx, vals, y, ids)."""
    from difacto_tpu.base import reverse_bytes
    from difacto_tpu.data import BatchReader, compact
    blk = next(iter(BatchReader(rcv1_path, batch_size=100)))
    cblk, uniq, _ = compact(blk)
    ids = reverse_bytes(uniq).astype(np.float64)   # the original ids
    counts = np.diff(cblk.offset)
    F = int(counts.max())
    idx = np.zeros((100, F), np.int32)
    vals = np.zeros((100, F), np.float32)
    for r in range(100):
        lo, hi = cblk.offset[r], cblk.offset[r + 1]
        idx[r, :hi - lo] = cblk.index[lo:hi]
        vals[r, :hi - lo] = cblk.value[lo:hi]
    return idx, vals, cblk.label.astype(np.float32), ids


def _state(w, V):
    n = len(w)
    z = jnp.zeros((n,), jnp.float32)
    V = jnp.zeros((n, 1), jnp.float32) if V is None else jnp.asarray(V)
    return ref.State(w=jnp.asarray(w), z=z, sg=z, cnt=z,
                     live=jnp.ones((n,), bool), V=V, Vg=jnp.zeros_like(V))


def test_golden_loss_and_gradient_without_V(rcv1_batch):
    idx, vals, y, ids = rcv1_batch
    h = ref.Hyper(V_dim=1)
    s = _state((ids / 5e4).astype(np.float32), None)
    loss, gw, gV = ref.gradients(h, s, jnp.asarray(idx), jnp.asarray(y),
                                 jnp.asarray(vals))
    assert abs(float(loss) - 147.4672) < 1e-3
    norm2 = float(np.sum(np.asarray(gw, np.float64) ** 2))
    assert abs(norm2 - 90.5817) < 1e-3
    assert float(jnp.abs(gV).max()) == 0.0


def test_golden_loss_and_gradient_with_V5(rcv1_batch):
    idx, vals, y, ids = rcv1_batch
    h = ref.Hyper(V_dim=5)
    V = np.stack([ids * (j + 1) / 5e5 for j in range(5)], 1)
    s = _state((ids / 5e4).astype(np.float32), V.astype(np.float32))
    loss, gw, gV = ref.gradients(h, s, jnp.asarray(idx), jnp.asarray(y),
                                 jnp.asarray(vals))
    assert abs(float(loss) - 330.628) < 1e-3
    norm2 = float(np.sum(np.asarray(gw, np.float64) ** 2)
                  + np.sum(np.asarray(gV, np.float64) ** 2))
    assert abs(norm2 - 1237.8) < 1e-1


def _three_steps(lower=None, n=200, k=4, l1=1e-4):
    rng = np.random.default_rng(0)
    h = ref.Hyper(V_dim=k, lr=0.1, l1=l1, V_threshold=0)
    V0 = jnp.asarray((rng.random((n, k), np.float32) - 0.5) * 0.01)
    batches = [(rng.integers(0, n, (32, 6)).astype(np.int32),
                (rng.random(32) < 0.3).astype(np.float32))
               for _ in range(3)]
    return ref.follow(h, V0, batches, lower=lower)


def test_follow_first_steps():
    out = _three_steps()
    # every w is 0 before step 1: the loss is rows * log 2, no embedding
    # takes part, and V's first gradient comes with step 2
    assert out["loss"][0] == pytest.approx(32 * np.log(2), rel=1e-6)
    assert out["grad"]["w"] > 0 and out["grad"]["V"] > 0
    assert out["change"]["w"] > 0 and out["change"]["V"] > 0
    assert out["live"] == out["nnz_w"] > 0


def test_follow_first_steps_of_the_flat_table():
    """V_dim = 0, l1 logistic regression: the same three steps on a V of
    no columns; the numbers are of w, z and sqrt_g alone."""
    from perfbench import check
    out = _three_steps(k=0, l1=0.3)
    assert out["loss"][0] == pytest.approx(32 * np.log(2), rel=1e-6)
    assert set(out["grad"]) == set(out["change"]) == {"w"}
    assert out["grad"]["w"] > 0 and out["change"]["w"] > 0
    rows = out["rows"]
    assert rows["V"].shape == rows["Vg"].shape == (200, 0)
    w, z, sg = (np.asarray(rows[k]) for k in ("w", "z", "sg"))
    # FTRL's soft threshold: w is exactly 0 wherever |z| <= l1, and some
    # touched rows (sqrt_g > 0) are such
    assert ((w == 0) == (np.abs(z) <= 0.3)).all()
    assert 0 < out["nnz_w"] == (w != 0).sum() < (sg > 0).sum()
    # against itself every number is 0; the fused rows' names are absent
    nums = check.numbers(out, out, ref.rel_diff)
    assert set(nums) == set(check.names(0)) and set(nums.values()) == {0.0}
    # the same rows, the linear part alone: an FM whose embeddings never
    # go live (V_threshold out of reach) takes the same steps
    rng = np.random.default_rng(0)      # a V of no columns drew nothing
    h = ref.Hyper(V_dim=4, lr=0.1, l1=0.3, V_threshold=1e9)
    batches = [(rng.integers(0, 200, (32, 6)).astype(np.int32),
                (rng.random(32) < 0.3).astype(np.float32))
               for _ in range(3)]
    V0 = jnp.asarray((rng.random((200, 4), np.float32) - 0.5) * 0.01)
    fm = ref.follow(h, V0, batches)
    assert fm["loss"] == out["loss"] and fm["live"] == 0
    assert (np.asarray(fm["rows"]["w"]) == w).all()


def test_flat_reference_without_l1_reads_apart():
    """The flat table's control: the soft threshold left out, so no
    weight that a batch touched is exactly 0; ``zero_w`` is the share."""
    from perfbench import check
    sound, bad = _three_steps(k=0, l1=0.3), _three_steps(k=0, l1=0.0)
    w = np.asarray(bad["rows"]["w"])
    assert ((w == 0) == (np.asarray(bad["rows"]["z"]) == 0)).all()
    nums = check.numbers(bad, sound, ref.rel_diff)
    assert nums["loss1"] == 0.0 and nums["grad_w"] == 0.0
    upd = np.asarray(sound["rows"]["sg"]) != 0
    differ = (w == 0) != (np.asarray(sound["rows"]["w"]) == 0)
    assert nums["zero_w"] == differ.sum() / upd.sum() > 0.05
    assert nums["round_w"] > 0.05


def test_reference_in_bfloat16_reads_apart():
    """The reference put in the program's place at a lower precision: its
    V and Vg rounded to bfloat16 wherever a table would store them."""
    def bf16(V, Vg):
        return (V.astype(jnp.bfloat16).astype(jnp.float32),
                Vg.astype(jnp.bfloat16).astype(jnp.float32))
    a, b = _three_steps(), _three_steps(lower=bf16)
    gap = abs(a["change"]["V"] - b["change"]["V"]) / a["change"]["V"]
    assert 1e-5 < gap < 0.1
    assert a["loss"][0] == b["loss"][0]


def test_touched_pads_with_the_row_no_feature_has():
    rows, idx = ref.touched([np.array([[5, 9], [9, 2]]),
                             np.array([[7, 5], [2, 2]])], pad_to=8)
    assert list(rows) == [2, 5, 7, 9, 0, 0, 0, 0]
    assert idx[0].tolist() == [[1, 3], [3, 0]]
    assert idx[1].tolist() == [[2, 1], [0, 0]]


def test_hyper_reads_a_configuration():
    h = ref.Hyper.of({"V_dim": 64, "lr": 0.1, "l1": "1e-4", "loss": "fm",
                      "V_threshold": 0, "V_dtype": "bfloat16",
                      "hash_capacity": 8388608, "batch_size": 65536})
    assert (h.V_dim, h.lr, h.l1, h.V_threshold) == (64, 0.1, 1e-4, 0.0)
    assert h.V_lr == 0.01 and h.l1_shrk and h.V_dtype == "bfloat16"


def _pair_case(n=300, k=4, l1=1e-4):
    """A state some steps into training, as host arrays, and two
    batches that share rows."""
    rng = np.random.default_rng(3)
    h = ref.Hyper(V_dim=k, lr=0.1, l1=l1, V_threshold=0)
    s = ref.initial_state(jnp.asarray(
        (rng.random((n, k), np.float32) - 0.5) * 0.01))
    mk = lambda: (rng.integers(0, n, (32, 6)).astype(np.int32),      # noqa
                  (rng.random(32) < 0.3).astype(np.float32))
    for _ in range(4):
        s, _ = ref.step(h, s, *map(jnp.asarray, mk()))
    before = {f: np.asarray(getattr(s, f)) for f in s._fields}
    return h, s, before, [mk(), mk()]


PAIR_NAMES = {4: {"pair_loss1", "pair_loss2", "pair_change_w",
                  "pair_change_V", "pair_round_V"},
              0: {"pair_loss1", "pair_loss2", "pair_change_w",
                  "pair_round_w"}}


@pytest.mark.parametrize("k", [4, 0], ids=["fused", "flat"])
def test_follow_pair_is_two_replayed_steps(k):
    from perfbench import check
    h, s, before, batches = _pair_case(k=k, l1=0.3 if k == 0 else 1e-4)
    out = ref.follow_pair(h, before, batches)
    s1, la = ref.step(h, s, *map(jnp.asarray, batches[0]), push_counts=False)
    s2, lb = ref.step(h, s1, *map(jnp.asarray, batches[1]),
                      push_counts=False)
    # (jitted against eager: equal to float32 rounding)
    assert out["loss"] == pytest.approx([float(la), float(lb)], rel=1e-6)
    assert np.allclose(out["after"]["V"], np.asarray(s2.V), rtol=1e-5,
                       atol=1e-9)
    assert (before["cnt"] == np.asarray(s2.cnt)).all()   # no counts pushed
    assert np.allclose(out["after"]["w"], np.asarray(s2.w), rtol=1e-5,
                       atol=1e-9)
    prog = dict(out, before=before)
    nums = ref.pair_numbers(prog, out, check.gap)
    assert set(nums) == PAIR_NAMES[k] and set(nums.values()) == {0.0}


@pytest.mark.parametrize("k", [4, 0], ids=["fused", "flat"])
@pytest.mark.parametrize("fault, sees", [
    ("stale", {"pair_loss2", "pair_change_w", "pair_change_V",
               "pair_round_V", "pair_round_w"}),
    ("half_batch", {"pair_loss1", "pair_loss2", "pair_change_w"})])
def test_planted_pair_faults_read_apart(fault, sees, k):
    from perfbench import check
    h, _, before, batches = _pair_case(k=k)
    sees = sees & PAIR_NAMES[k]
    sound = ref.follow_pair(h, before, batches)
    bad = ref.follow_pair(h, before, batches, fault=fault)
    nums = ref.pair_numbers(dict(bad, before=before), sound, check.gap)
    assert all(nums[n] > 1e-3 for n in sees), nums
    if fault == "stale":
        assert nums["pair_loss1"] == 0.0      # the first step is sound
