"""ISSUE 26: the row cap follows the rows.

(a) ``ops/batch.row_cap``: bucket()'s ladder with eighths between the
    powers of two, fine rungs only where they are multiples of 1024 rows
    above 8192;
(b) the property the gain rests on: a step run at two row caps is the
    same step — loss and table bit-equal, no row outside the batch's
    slots written — through ``packed_panel_train_chunked`` and through
    the paired replay program, V16 float32 and V64 bfloat16;
(c) the sticky schedule gives ``<job>.u`` the fine ladder and nothing
    else, keeps an absorbed old-ladder cap, and ``_enqueue`` counts rows
    and caps for every step, replayed pairs included.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import write_uniform_libsvm

from difacto_tpu.data.pack_stream import ShapeSchedule, prepare_hashed
from difacto_tpu.data.rowblock import RowBlock
from difacto_tpu.learners import Learner
from difacto_tpu.learners.sgd import K_TRAINING
from difacto_tpu.ops.batch import (bucket, chunk_cap, mesh_dim_min,
                                   row_cap)

FINE_ABOVE = 8192
TOP = 1 << 21


def _old_rungs(top: int = TOP) -> list:
    out, b = [], 8
    while b <= top:
        out += [b, b + b // 2]
        b *= 2
    return [r for r in out if r <= top]


def _eighths(top: int = TOP) -> list:
    """Every (8+i)/8 * 2^j up to ``top``, fine or not."""
    out, b = set(), 8
    while b <= top:
        out.update(b * (8 + i) // 8 for i in range(8))
        b *= 2
    return sorted(r for r in out if r <= top)


# the boundaries, one below and one above, plus the sizes of the cells
POINTS = sorted(set(range(1, 71)) | {279_000, 283_600} | {
    n for r in _eighths() for n in (r - 1, r, r + 1) if 1 <= n <= TOP})


# one case a doubling: (2^j, 2^(j+1)], the first from 1
BANDS = [(0 if j == 3 else 1 << j, 1 << (j + 1)) for j in range(3, 21)]


def _band(lo: int, hi: int) -> list:
    pts = [n for n in POINTS if lo < n <= hi]
    assert pts
    return pts


@pytest.mark.parametrize("lo,hi", BANDS)
def test_row_cap_covers_is_monotone_and_idempotent(lo, hi):
    pts = _band(lo, hi)
    caps = [row_cap(n) for n in pts]
    assert all(n <= c <= hi for n, c in zip(pts, caps))
    assert caps == sorted(caps)
    assert all(row_cap(c) == c for c in caps)


@pytest.mark.parametrize("lo,hi", [b for b in BANDS if b[1] <= FINE_ABOVE])
def test_row_cap_is_bucket_for_small_shapes(lo, hi):
    """The tiny shapes of the tier-1 tests, and the programs they
    compile, do not change."""
    for n in _band(lo, hi):
        assert bucket(n) <= FINE_ABOVE and row_cap(n) == bucket(n), n


@pytest.mark.parametrize("lo,hi", [b for b in BANDS if b[0] >= FINE_ABOVE])
def test_row_cap_fine_rungs_tile_and_bound_the_padding(lo, hi):
    pts = _band(lo, hi)
    for n in pts:
        c = row_cap(n)
        assert c % 1024 == 0, n
        assert c <= 1.125 * n + 1024, n
        assert c <= bucket(n), n
    # all eight rungs of the doubling are in use
    assert len({row_cap(n) for n in pts}) == 8


def test_row_cap_of_the_cells_rows():
    assert row_cap(279_000) == row_cap(283_600) == 294_912
    assert bucket(279_000) == 393_216
    # the chunk cap follows by itself (65536 x 39 cells, L = 16)
    assert chunk_cap(294_912, 65536 * 39) == 454_658


@pytest.mark.parametrize("rung", _old_rungs())
def test_every_bucket_rung_is_still_a_row_cap_rung(rung):
    """A cap absorbed from an older snapshot stays valid."""
    assert row_cap(rung) == rung


@pytest.mark.parametrize("dp", [1, 2, 3, 4, 5, 6, 8])
def test_row_cap_divisible_under_mesh_dim_min(dp):
    """The shape of test_mesh_dim_min_divisibility: should a sharded
    dimension ever take this ladder, every rung from mesh_dim_min(dp)
    divides by dp."""
    m = mesh_dim_min(dp)
    for n in list(range(1, 70)) + [100, 1000, 12345, 279_000, 1 << 20] \
            + [r + 1 for r in _eighths(1 << 19)]:
        c = row_cap(n, m)
        assert c >= n and c % dp == 0, (dp, n, c)
        assert c <= bucket(n, m)
        if c > FINE_ABOVE and c not in (bucket(n, m),):
            assert c % 1024 == 0


# ------------------------------------------------- (b) padding invariance
CAPACITY = 1 << 15
B, WIDTH = 256, 40


def _learner(V_dim: int, V_dtype: str, data: str):
    args = dict(data_in=data, V_dim=V_dim, V_dtype=V_dtype, V_threshold=0,
                lr=0.1, l1=1e-4, l2=0, batch_size=B, shuffle=0,
                num_jobs_per_epoch=1, report_interval=0, stop_rel_objv=0,
                hash_capacity=CAPACITY, producer_mode="thread",
                device_cache_mb=0, seed=3)
    ln = Learner.create("sgd")
    assert ln.init([(k, str(v)) for k, v in args.items()]) == []
    return ln


def _block(seed: int) -> RowBlock:
    """A full binary batch (criteo's shape): B rows of WIDTH features,
    ~9,000 distinct table rows of the 2^15."""
    rng = np.random.RandomState(seed)
    return RowBlock(
        offset=np.arange(B + 1, dtype=np.int64) * WIDTH,
        label=rng.randint(0, 2, B).astype(np.float32),
        index=rng.randint(1, 1 << 40, B * WIDTH).astype(np.uint64))


def _staged(ln, blk: RowBlock, u_cap: int):
    """The batch as the cache stages it, packed at row cap ``u_cap``."""
    shapes = ShapeSchedule()
    shapes.absorb({"train.u": u_cap})
    kind, i32, f32, binary, b_cap, width, got = prepare_hashed(
        shapes, CAPACITY, blk, want_counts=False, fill_counts=False,
        dim_min=8, job="train", b_cap=B)
    assert (kind, got, b_cap, width) == ("panel", u_cap, B, WIDTH)
    n_uniq = int(i32[-1])
    slots = i32[B * WIDTH:B * WIDTH + n_uniq]
    i32, f32 = jnp.asarray(i32), jnp.asarray(f32)
    chunks = ln._panel_chunk_packed(i32, f32, B, WIDTH, u_cap, binary)
    return (i32, f32, chunks), binary, slots


def _copy(state):
    return jax.tree_util.tree_map(jnp.copy, state)


def _bits(x) -> np.ndarray:
    a = np.ascontiguousarray(np.asarray(x))
    return a.reshape(a.shape or (1,)).view(np.uint8)


@pytest.mark.parametrize("V_dim,V_dtype", [(16, "float32"),
                                           (64, "bfloat16")])
@pytest.mark.parametrize("program", ["single", "pair"])
def test_step_is_the_same_step_at_two_row_caps(tmp_path, V_dim, V_dtype,
                                               program):
    data = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=8)
    ln = _learner(V_dim, V_dtype, data)
    blocks = [_block(11), _block(12)]
    start = _copy(ln.store.state)
    outs = []
    for u_cap in (9216, 12288):
        assert row_cap(9000) == 9216 and bucket(9000) == 12288
        staged = [_staged(ln, b, u_cap) for b in blocks]
        (pa, binary, slots_a), (pb, _, slots_b) = staged
        assert 8192 < len(slots_a) <= 9216 and 8192 < len(slots_b) <= 9216
        state = _copy(start)
        if program == "single":
            state, o1, _ = ln._packed_panel_train_chunked(
                state, *pa, B, WIDTH, u_cap, False, binary)
            losses, touched = [o1], slots_a
        else:
            state, o1, _, o2, _ = ln._packed_panel_train_chunked2(
                state, pa, pb, B, WIDTH, u_cap, False, binary)
            losses = [o1, o2]
            touched = np.union1d(slots_a, slots_b)
        outs.append((losses, state, touched))
    (loss_a, state_a, touched), (loss_b, state_b, _) = outs
    for x, y in zip(loss_a + jax.tree_util.tree_leaves(state_a),
                    loss_b + jax.tree_util.tree_leaves(state_b)):
        np.testing.assert_array_equal(_bits(x), _bits(y))
    # the step did something, and only to the batch's rows
    before, after = _bits(start.VVg), _bits(state_a.VVg)
    changed = np.flatnonzero((after != before).any(axis=1))
    assert len(changed) > 0.9 * len(touched)
    assert np.isin(changed, touched).all()


# ------------------------------------------- (c) schedule and counters
def test_schedule_gives_the_row_dimension_the_fine_ladder():
    s = ShapeSchedule()
    assert s.row_cap("train", 279_000) == 294_912
    assert s.snapshot() == {"train.u": 294_912}
    assert s.row_cap("eval", 9000) == 9216
    # the other dimensions, and the request path's rows, keep bucket()
    assert s.cap("train.b", 279_000) == 393_216
    assert s.cap("train.nnz", 279_000) == 393_216
    assert s.cap("serve.u", 279_000) == 393_216
    assert ShapeSchedule().cap("serve.u", 9000) == 12288
    # sticky: smaller batches stay, a larger one climbs one fine rung
    assert s.row_cap("train", 100) == 294_912
    assert s.row_cap("train", 294_913) == 327_680


def test_schedule_gives_the_chunk_dimension_its_own_sticky_cap():
    """ISSUE 30: ``<job>.c`` follows the chunks a batch needs, on the
    ladder of ``<job>.u``, under the static bound."""
    cells = 65536 * 39
    # the static bound is what it was: any batch of the shape fits it
    assert chunk_cap(294_912, cells) == 454_658
    s = ShapeSchedule()
    assert s.row_cap("train", 279_000) == 294_912
    # the cells' traffic: 199,7xx-199,8xx chunks a batch
    assert s.chunk_cap("train", 199_800, 294_912, cells) == 212_992
    assert s.snapshot() == {"train.u": 294_912, "train.c": 212_992}
    # sticky: fewer chunks stay, more climb one fine rung
    assert s.chunk_cap("train", 150_000, 294_912, cells) == 212_992
    assert s.chunk_cap("train", 0, 294_912, cells) == 212_992
    assert s.chunk_cap("train", 212_993, 294_912, cells) == 229_376
    # a worker seeded from a snapshot packs at the consumer's cap
    w = ShapeSchedule()
    w.absorb(s.snapshot())
    assert w.chunk_cap("train", 10, 294_912, cells) == 229_376
    # another job's cap is its own; a batch that needs none still gets a
    # non-empty dimension
    assert s.chunk_cap("eval", 0, 9216, 256 * 40) == 8
    # never above the static bound, whatever the rung
    assert s.chunk_cap("train", 454_000, 294_912, cells) == 454_658
    assert row_cap(454_000) == 458_752
    # small shapes keep the static bound, whatever they need, and leave
    # no sticky key: no rung to climb, nothing to recompile
    tiny = ShapeSchedule()
    assert tiny.chunk_cap("train", 3, 64, 16) == chunk_cap(64, 16) == 67
    assert tiny.chunk_cap("train", 3, 4096, 65536 - 32) == 8192
    assert tiny.snapshot() == {}
    assert chunk_cap(4096, 65536) == 8194
    assert tiny.chunk_cap("train", 3, 4096, 65536) == 8
    assert tiny.snapshot() == {"train.c": 8}
    # a mesh's dp axis divides it
    for dp in (1, 2, 3, 4, 8):
        c = ShapeSchedule().chunk_cap("train", 199_800, 294_912, cells, dp)
        assert c % dp == 0 and 212_992 <= c < 212_992 + dp


@pytest.mark.parametrize("lo,hi", [b for b in BANDS if b[0] >= FINE_ABOVE])
def test_chunk_cap_rides_row_caps_ladder_under_the_bound(lo, hi):
    """For every point of the doubling: the sticky chunk cap of a fresh
    schedule is ``row_cap`` of the count, cut at the static bound."""
    cells = 65536 * 39
    for n in _band(lo, hi):
        for u_cap in (row_cap(n), 294_912):
            got = ShapeSchedule().chunk_cap("train", n, u_cap, cells)
            assert got == min(row_cap(n), chunk_cap(u_cap, cells))
            assert got >= min(n, chunk_cap(u_cap, cells))


def test_absorbed_old_ladder_cap_is_kept():
    s = ShapeSchedule()
    s.absorb({"train.u": 393_216})
    assert s.row_cap("train", 279_000) == 393_216
    assert s.snapshot()["train.u"] == 393_216
    # a worker that starts from that snapshot packs at the same cap
    w = ShapeSchedule()
    w.absorb(s.snapshot())
    assert w.row_cap("train", 283_600) == 393_216


def test_serve_executor_rows_keep_buckets_ladder(tmp_path):
    """``serve.u`` compiles on the request path: bucket()'s rungs."""
    from difacto_tpu.serve.executor import PredictExecutor
    data = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=8)
    ln = _learner(4, "float32", data)
    ex = PredictExecutor(ln.store, loss=ln.loss)
    scores, _, _ = ex.predict(_block(11))
    assert len(scores) == B
    caps = ex._shapes.snapshot()
    assert caps["serve.u"] == 12288 and row_cap(9000) == 9216


def _wait_pair_compile():
    for t in threading.enumerate():
        if t.name == "pair-exec-compile":
            t.join()


def _fill(ln) -> dict:
    return {"cap": ln.obs.value("step_row_cap_total", job="train"),
            "rows": ln.obs.value("step_rows_total", job="train"),
            "ccap": ln.obs.value("step_chunk_cap_total", job="train"),
            "chunks": ln.obs.value("step_chunks_total", job="train"),
            "paired": getattr(ln, "_paired_dispatches", 0)}


def test_counters_advance_by_rows_and_cap_per_step(tmp_path):
    """Streamed epoch 0, then replayed epochs that pair: every enqueued
    step adds its cap and its distinct rows, on the replay path too."""
    path = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=128)
    args = dict(data_in=path, V_dim=4, V_threshold=0, lr=0.1, l1=1e-4,
                l2=0, num_jobs_per_epoch=1, batch_size=32,
                max_num_epochs=5, shuffle=0, report_interval=0,
                stop_rel_objv=0, hash_capacity=2048,
                producer_mode="thread", device_cache_mb=16)
    ln = Learner.create("sgd")
    assert ln.init([(k, str(v)) for k, v in args.items()]) == []
    at_end = []

    def on_end(epoch, train, _val):
        _wait_pair_compile()
        at_end.append(_fill(ln))

    ln.add_epoch_end_callback(on_end)
    ln.run()
    steps = 128 // 32
    cache = ln._dev_caches[K_TRAINING]
    items = [pl for part in cache.entries.values() for pl in part]
    assert len(items) == steps
    u_cap = items[0][6]
    rows = sum(pl[-1] for pl in items)
    # what each cached batch says of itself is what its buffer holds
    for pl in items:
        assert pl[0] == "panel_chunked" and pl[6] == u_cap
        assert pl[-1] == int(np.asarray(pl[1])[-1]) <= u_cap
    assert u_cap == ln._shapes.snapshot()["train.u"] == row_cap(
        max(pl[-1] for pl in items))
    # epoch 0 (streamed and staged)
    assert at_end[0]["cap"] == steps * u_cap
    assert at_end[0]["rows"] == rows
    # the last epoch ran wholly in pairs, and is counted the same
    before, after = at_end[-2], at_end[-1]
    assert after["paired"] - before["paired"] == steps // 2
    assert after["cap"] - before["cap"] == steps * u_cap
    assert after["rows"] - before["rows"] == rows
    assert 0 < after["rows"] / after["cap"] <= 1
    assert ln.obs.value("step_row_cap_total", job="eval") == 0
    # ISSUE 30: the chunk dimension is counted the same way, from what
    # each cached batch says of itself: the chunks its lanes need (a
    # prefix of its chunk_lane) under the cap it was staged at
    from difacto_tpu.ops.batch import chunks_needed
    caps = [pl[3][1].shape[0] for pl in items]
    need = [pl[10] for pl in items]
    for pl, c, n in zip(items, caps, need):
        lanes = np.asarray(pl[1])[:pl[4] * pl[5]]
        assert n == chunks_needed(lanes, u_cap) <= c
        assert n == int((np.asarray(pl[3][1]) < u_cap).sum())
        assert len(pl[3]) == 5 and pl[3][3].shape == (u_cap,)
    # shapes this small keep the static bound
    assert caps == [chunk_cap(u_cap, items[0][4] * items[0][5])] * steps
    assert "train.c" not in ln._shapes.snapshot()
    assert at_end[0]["ccap"] == sum(caps)
    assert at_end[0]["chunks"] == sum(need) > 0
    assert after["ccap"] - before["ccap"] == sum(caps)
    assert after["chunks"] - before["chunks"] == sum(need)
    assert ln.obs.value("step_chunk_cap_total", job="eval") == 0


def test_counters_on_the_coo_and_eval_paths(rcv1_path):
    """Ragged rows pack as COO (meta tail ``[b, nu, nnz]``) and a
    validation pass counts under job=eval."""
    args = dict(data_in=rcv1_path, data_val=rcv1_path,
                data_format="libsvm", V_dim=0, lr=1, l1=1, l2=1,
                batch_size=25, num_jobs_per_epoch=1, shuffle=0,
                max_num_epochs=2, stop_rel_objv=0, report_interval=0,
                hash_capacity=1 << 14, device_cache_mb=16)
    ln = Learner.create("sgd")
    assert ln.init([(k, str(v)) for k, v in args.items()]) == []
    ln.run()
    for job in ("train", "eval"):
        cap = ln.obs.value("step_row_cap_total", job=job)
        rows = ln.obs.value("step_rows_total", job=job)
        assert 0 < rows <= cap, (job, rows, cap)
    caches = [c for c in ln._dev_caches.values() if c.entries]
    assert caches
    for c in caches:
        for part in c.entries.values():
            for pl in part:
                assert pl[0] == "coo"
                nu = int(np.asarray(pl[1])[-2])
                assert pl[-1] == nu <= pl[5]
