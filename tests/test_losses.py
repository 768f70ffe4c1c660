"""Loss kernel tests against the reference's golden constants.

Mirrors tests/cpp/fm_loss_test.cc: build deterministic weights indexed by the
original feature id over the first 100-row rcv1 batch, check the logit
objective and squared gradient norm. Golden values from the reference suite
(fm_loss_test.cc:35-39, 78-82): NoV 147.4672 / 90.5817; HasV(V_dim=5)
330.628 / 1237.8.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from difacto_tpu.base import reverse_bytes
from difacto_tpu.data import BatchReader, compact
from difacto_tpu.losses import FMParams, create, metrics
from difacto_tpu.losses.fm import fm_grad, fm_predict, logit_objv
from difacto_tpu.ops import pad_batch, spmv, spmv_t


@pytest.fixture(scope="module")
def batch100(rcv1_path):
    blk = next(iter(BatchReader(rcv1_path, batch_size=100)))
    cblk, uniq, _ = compact(blk)
    orig_ids = reverse_bytes(uniq)  # original feature ids, like utils.h:126-136
    dev = pad_batch(cblk, num_uniq=len(uniq))
    return dev, orig_ids, cblk


def test_fm_loss_nov_golden(batch100):
    dev, ids, _ = batch100
    U = len(ids)
    w = np.zeros(dev.cols.max() + 1 if U == 0 else U, dtype=np.float32)
    w[:] = ids.astype(np.float64) / 5e4
    params = FMParams(w=jnp.asarray(w))
    pred = fm_predict(params, dev)
    objv = float(logit_objv(pred, dev))
    assert abs(objv - 147.4672) < 1e-3

    gw, gV = fm_grad(params, dev, pred)
    assert gV is None
    norm2 = float(np.sum(np.asarray(gw, dtype=np.float64) ** 2))
    assert abs(norm2 - 90.5817) < 1e-3


def test_fm_loss_hasv_golden(batch100):
    dev, ids, _ = batch100
    V_dim = 5
    U = len(ids)
    w = (ids.astype(np.float64) / 5e4).astype(np.float32)
    V = np.empty((U, V_dim), dtype=np.float32)
    for j in range(V_dim):
        V[:, j] = (ids.astype(np.float64) * (j + 1) / 5e5)
    params = FMParams(w=jnp.asarray(w), V=jnp.asarray(V))
    pred = fm_predict(params, dev)
    objv = float(logit_objv(pred, dev))
    assert abs(objv - 330.628) < 1e-3

    gw, gV = fm_grad(params, dev, pred)
    norm2 = float(np.sum(np.asarray(gw, dtype=np.float64) ** 2)
                  + np.sum(np.asarray(gV, dtype=np.float64) ** 2))
    assert abs(norm2 - 1237.8) < 1e-1


def test_fm_vs_dense_brute_force():
    """FM forward/backward vs a dense numpy re-derivation on random data."""
    rng = np.random.RandomState(0)
    B, U, k, nnz_per_row = 16, 30, 4, 5
    rows, cols, vals = [], [], []
    for r in range(B):
        cs = rng.choice(U, nnz_per_row, replace=False)
        for c in cs:
            rows.append(r); cols.append(c); vals.append(rng.randn())
    X = np.zeros((B, U))
    for r, c, v in zip(rows, cols, vals):
        X[r, c] = v
    w = rng.randn(U).astype(np.float32)
    V = (rng.randn(U, k) * 0.1).astype(np.float32)
    label = rng.choice([0.0, 1.0], B).astype(np.float32)

    from difacto_tpu.data.rowblock import RowBlock
    order = np.lexsort((cols, rows))
    r_s = np.array(rows)[order]; c_s = np.array(cols)[order]
    v_s = np.array(vals)[order].astype(np.float32)
    offset = np.zeros(B + 1, dtype=np.int64)
    for r in r_s:
        offset[r + 1] += 1
    np.cumsum(offset, out=offset)
    blk = RowBlock(offset=offset, label=label,
                   index=c_s.astype(np.uint32), value=v_s)
    dev = pad_batch(blk, num_uniq=U)

    params = FMParams(w=jnp.asarray(w), V=jnp.asarray(V))
    pred = np.asarray(fm_predict(params, dev))[:B]

    XV = X @ V
    dense_pred = X @ w + 0.5 * ((XV ** 2).sum(1) - (X ** 2) @ (V ** 2).sum(1))
    dense_pred = np.clip(dense_pred, -20, 20)
    np.testing.assert_allclose(pred, dense_pred, rtol=2e-5, atol=2e-5)

    gw, gV = fm_grad(params, dev, jnp.asarray(np.asarray(fm_predict(params, dev))))
    y = np.where(label > 0, 1.0, -1.0)
    p = -y / (1 + np.exp(y * dense_pred))
    dense_gw = X.T @ p
    dense_gV = X.T @ (p[:, None] * XV) - ((X ** 2).T @ p)[:, None] * V
    np.testing.assert_allclose(np.asarray(gw), dense_gw, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(gV), dense_gV, rtol=2e-4, atol=2e-5)


def test_v_mask_matches_absent_embeddings(batch100):
    """v_mask zeroes both the forward contribution and the V gradient —
    the reference's V_pos == -1 semantics (fm_loss.h:97-99,186-191)."""
    dev, ids, _ = batch100
    U = len(ids)
    rng = np.random.RandomState(1)
    w = rng.randn(U).astype(np.float32) * 0.01
    V = rng.randn(U, 3).astype(np.float32) * 0.1
    mask = (rng.random_sample(U) < 0.5).astype(np.float32)

    pm = FMParams(w=jnp.asarray(w), V=jnp.asarray(V), v_mask=jnp.asarray(mask))
    pz = FMParams(w=jnp.asarray(w), V=jnp.asarray(V * mask[:, None]))
    pred_m = np.asarray(fm_predict(pm, dev))
    pred_z = np.asarray(fm_predict(pz, dev))
    np.testing.assert_allclose(pred_m, pred_z, rtol=1e-6)

    _, gV_m = fm_grad(pm, dev, jnp.asarray(pred_m))
    assert np.all(np.asarray(gV_m)[mask == 0] == 0)


def test_spmv_roundtrip_identity():
    rng = np.random.RandomState(2)
    nnz, B, U = 64, 8, 12
    rows = jnp.asarray(rng.randint(0, B, nnz), dtype=jnp.int32)
    cols = jnp.asarray(rng.randint(0, U, nnz), dtype=jnp.int32)
    vals = jnp.asarray(rng.randn(nnz), dtype=jnp.float32)
    x = jnp.asarray(rng.randn(U), dtype=jnp.float32)
    p = jnp.asarray(rng.randn(B), dtype=jnp.float32)
    # <Ax, p> == <x, A'p>
    lhs = float(jnp.dot(spmv(vals, rows, cols, x, B), p))
    rhs = float(jnp.dot(x, spmv_t(vals, rows, cols, p, U)))
    assert abs(lhs - rhs) < 1e-3


def test_auc_device_matches_host(batch100):
    dev, _, cblk = batch100
    rng = np.random.RandomState(3)
    pred = rng.randn(dev.batch_cap).astype(np.float32)
    host = metrics.auc_times_n(cblk.label, pred[:cblk.size])
    devv = float(metrics.auc_times_n_jnp(
        dev.labels, jnp.asarray(pred), dev.row_mask))
    assert abs(host - devv) < 1e-3
    # degenerate: all positive
    assert metrics.auc_times_n(np.ones(5), rng.randn(5)) == 1.0


def test_loss_factory():
    assert create("logit", 7).V_dim == 0
    assert create("fm", 7).V_dim == 7
    with pytest.raises(ValueError):
        create("hinge")


def test_panel_matches_coo():
    """PanelBatch kernels reproduce the COO kernels on ragged data
    (uniform-width binary AND ragged weighted rows)."""
    import numpy as np
    import jax.numpy as jnp
    from difacto_tpu.data.rowblock import RowBlock
    from difacto_tpu.losses import FMParams, fm_grad, fm_grad_panel, \
        fm_predict, fm_predict_panel
    from difacto_tpu.ops.batch import pad_batch, pad_panel, panel_width

    rng = np.random.RandomState(7)
    U, k, B = 64, 4, 16

    def check(blk, num_uniq, width):
        w = jnp.asarray(rng.randn(U).astype(np.float32))
        V = jnp.asarray(rng.randn(U, k).astype(np.float32) * 0.1)
        vm = jnp.asarray((rng.rand(U) > 0.3).astype(np.float32))
        params = FMParams(w=w, V=V, v_mask=vm)
        coo = pad_batch(blk, num_uniq=num_uniq, batch_cap=B)
        pb = pad_panel(blk, num_uniq, B, width)
        pred_c = fm_predict(params, coo)
        pred_p = fm_predict_panel(params, pb)
        mask = np.asarray(coo.row_mask) > 0
        np.testing.assert_allclose(np.asarray(pred_c)[mask],
                                   np.asarray(pred_p)[mask], rtol=1e-5)
        gw_c, gV_c = fm_grad(params, coo, pred_c)
        gw_p, gV_p = fm_grad_panel(params, pb, pred_p)
        # rtol 5e-5: panel and COO sum token contributions in different
        # orders, and the widest case runs 70-term row sums
        np.testing.assert_allclose(np.asarray(gw_c), np.asarray(gw_p),
                                   rtol=5e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(gV_c), np.asarray(gV_p),
                                   rtol=5e-5, atol=1e-6)
        # linear (V=None) path too
        lp = FMParams(w=w, V=None, v_mask=None)
        np.testing.assert_allclose(
            np.asarray(fm_predict(lp, coo))[mask],
            np.asarray(fm_predict_panel(lp, pb))[mask], rtol=1e-5)

    # uniform-width binary rows (criteo shape), full batch
    F = 5
    blk_u = RowBlock(
        offset=np.arange(B + 1, dtype=np.int64) * F,
        label=rng.choice([0.0, 1.0], B).astype(np.float32),
        index=rng.randint(0, U, B * F).astype(np.uint32),
        value=None)
    assert panel_width(blk_u, B) == F  # uniform width is panel-eligible
    check(blk_u, U, F)

    # ragged weighted rows, partial batch (12 of 16)
    counts = rng.randint(1, 7, 12)
    off = np.zeros(13, dtype=np.int64)
    np.cumsum(counts, out=off[1:])
    blk_r = RowBlock(
        offset=off,
        label=rng.choice([0.0, 1.0], 12).astype(np.float32),
        index=rng.randint(0, U, off[-1]).astype(np.uint32),
        value=rng.rand(off[-1]).astype(np.float32),
        weight=rng.rand(12).astype(np.float32))
    check(blk_r, U, int(counts.max()))

    # wider than _COLLOOP_MAX_WIDTH: the forward's single-gather fallback
    from difacto_tpu.losses.fm import _COLLOOP_MAX_WIDTH
    Fw = _COLLOOP_MAX_WIDTH + 6
    blk_w = RowBlock(
        offset=np.arange(B + 1, dtype=np.int64) * Fw,
        label=rng.choice([0.0, 1.0], B).astype(np.float32),
        index=rng.randint(0, U, B * Fw).astype(np.uint32),
        value=None)
    check(blk_w, U, Fw)


def test_chunked_backward_matches_unsorted():
    """The chunked-run panel backward (panel_chunk_tokens +
    _fm_grad_panel_chunked) reproduces the unsorted scatter backward on
    binary, valued/ragged, and V=None panels, including zipf-skewed lanes
    (runs longer than CHUNK_L split across chunks)."""
    import numpy as np
    import jax.numpy as jnp
    from difacto_tpu.data.rowblock import RowBlock
    from difacto_tpu.losses import FMParams, fm_grad_panel, fm_predict_panel
    from difacto_tpu.ops.batch import pad_panel, panel_chunk_tokens

    rng = np.random.RandomState(12)
    U, k, B = 96, 6, 48

    def check(blk, width, V_dim):
        w = jnp.asarray(rng.randn(U).astype(np.float32))
        V = (jnp.asarray(rng.randn(U, V_dim).astype(np.float32) * 0.1)
             if V_dim else None)
        vm = jnp.asarray((rng.rand(U) > 0.3).astype(np.float32))
        params = FMParams(w=w, V=V, v_mask=vm if V_dim else None)
        pb = pad_panel(blk, U, B, width)
        pred = fm_predict_panel(params, pb)
        gw_u, gV_u = fm_grad_panel(params, pb, pred)
        pbc = panel_chunk_tokens(pb, U)
        assert pbc.chunk_lane is not None
        gw_c, gV_c = fm_grad_panel(params, pbc, pred)
        np.testing.assert_allclose(np.asarray(gw_u), np.asarray(gw_c),
                                   rtol=2e-5, atol=1e-6)
        if V_dim:
            np.testing.assert_allclose(np.asarray(gV_u), np.asarray(gV_c),
                                       rtol=2e-5, atol=1e-6)
        else:
            assert gV_u is None and gV_c is None

    # uniform binary rows with zipf-skewed lanes: hot lanes get token runs
    # far longer than CHUNK_L, exercising multi-chunk runs
    F = 7
    idx_z = ((rng.zipf(1.3, B * F) - 1) % U).astype(np.uint32)
    blk_z = RowBlock(
        offset=np.arange(B + 1, dtype=np.int64) * F,
        label=rng.choice([0.0, 1.0], B).astype(np.float32),
        index=idx_z,
        value=None)
    check(blk_z, F, V_dim=k)
    check(blk_z, F, V_dim=0)

    # ragged weighted rows, partial batch (pad rows + pad cells)
    counts = rng.randint(1, 7, 29)
    off = np.zeros(30, dtype=np.int64)
    np.cumsum(counts, out=off[1:])
    blk_r = RowBlock(
        offset=off,
        label=rng.choice([0.0, 1.0], 29).astype(np.float32),
        index=rng.randint(0, U, off[-1]).astype(np.uint32),
        value=rng.rand(off[-1]).astype(np.float32),
        weight=rng.rand(29).astype(np.float32))
    check(blk_r, int(counts.max()), V_dim=k)
    check(blk_r, int(counts.max()), V_dim=0)


def test_panel_chunk_layout_invariants():
    """panel_chunk_tokens_flat: chunk lanes ascend, every token row id
    appears exactly once among its lane's chunk cells, pads point out of
    bounds, and the layout stays within the static chunk_cap bound."""
    import numpy as np
    import jax.numpy as jnp
    from difacto_tpu.ops.batch import (CHUNK_L, chunk_cap,
                                       panel_chunk_tokens_flat)

    rng = np.random.RandomState(13)
    B, F, u_cap = 64, 5, 40
    flat = ((rng.zipf(1.3, B * F) - 1) % u_cap).astype(np.int32)
    ci, cl, cv = panel_chunk_tokens_flat(jnp.asarray(flat), None, u_cap,
                                         B, F)
    ci, cl = np.asarray(ci), np.asarray(cl)
    assert ci.shape == (chunk_cap(u_cap, B * F), CHUNK_L)
    used = cl < u_cap
    # used chunks form a prefix with ascending lanes
    assert used[:used.sum()].all()
    assert (np.diff(cl[used]) >= 0).all()
    # padded chunks carry no real cells
    assert (ci[~used] == B).all()
    # per lane: the multiset of (row) tokens matches the panel
    for lane in range(u_cap):
        toks = ci[cl == lane]
        toks = toks[toks < B]
        want = np.flatnonzero(flat == lane) // F
        np.testing.assert_array_equal(np.sort(toks), np.sort(want))


def test_numpy_chunker_and_unsorted_chunks_match():
    """panel_chunk_tokens_np (the host-side twin the mesh paths use)
    produces the same reduction as the jit chunker, including explicit-C
    rounding and row_base offsets; and the chunked backward with
    sorted_chunks=False (the dp>1 mesh setting) equals sorted_chunks=True."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from difacto_tpu.data.rowblock import RowBlock
    from difacto_tpu.losses import FMParams, fm_grad_panel, fm_predict_panel
    from difacto_tpu.ops.batch import (chunk_cap, pad_panel,
                                       panel_chunk_tokens,
                                       panel_chunk_tokens_np)

    rng = np.random.RandomState(5)
    B, F, u_cap = 48, 6, 40
    flat = ((rng.zipf(1.3, B * F) - 1) % u_cap).astype(np.int32)
    vals = rng.rand(B * F).astype(np.float32)

    from difacto_tpu.ops.batch import panel_chunk_tokens_flat
    ci_j, cl_j, cv_j = jax.jit(
        panel_chunk_tokens_flat, static_argnums=(2, 3, 4))(
            jnp.asarray(flat), jnp.asarray(vals), u_cap, B, F)
    ci_n, cl_n, cv_n = panel_chunk_tokens_np(flat, vals, u_cap, B, F)

    def reduce(ci, cl, cv, row_q, nrows):
        ci, cl, cv = np.asarray(ci), np.asarray(cl), np.asarray(cv)
        toks = np.where(ci[:, :, None] < nrows,
                        row_q[np.minimum(ci, nrows - 1)], 0.0)
        part = (toks * cv[:, :, None]).sum(axis=1)
        out = np.zeros((u_cap, row_q.shape[1]))
        m = cl < u_cap
        np.add.at(out, cl[m], part[m])
        return out

    row_q = rng.rand(B, 4)
    np.testing.assert_allclose(reduce(ci_j, cl_j, cv_j, row_q, B),
                               reduce(ci_n, cl_n, cv_n, row_q, B),
                               rtol=1e-5)

    # explicit C (mesh dp rounding) + row_base (global dp row space)
    C = -(-chunk_cap(u_cap, B * F) // 3) * 3
    ci2, cl2, cv2 = panel_chunk_tokens_np(flat, vals, u_cap, 2 * B, F,
                                          C=C, row_base=B)
    assert ci2.shape[0] == C
    rq2 = np.concatenate([np.zeros_like(row_q), row_q])
    np.testing.assert_allclose(reduce(ci2, cl2, cv2, rq2, 2 * B),
                               reduce(ci_j, cl_j, cv_j, row_q, B),
                               rtol=1e-5)

    # sorted_chunks=False backward (dp>1 meshes) == sorted backward
    k = 5
    blk = RowBlock(offset=np.arange(B + 1, dtype=np.int64) * F,
                   label=rng.choice([0.0, 1.0], B).astype(np.float32),
                   index=flat.astype(np.uint32),
                   value=vals)
    w = jnp.asarray(rng.randn(u_cap).astype(np.float32))
    V = jnp.asarray(rng.randn(u_cap, k).astype(np.float32) * 0.1)
    vm = jnp.asarray((rng.rand(u_cap) > 0.3).astype(np.float32))
    params = FMParams(w=w, V=V, v_mask=vm)
    pb = panel_chunk_tokens(pad_panel(blk, u_cap, B, F), u_cap)
    pred = fm_predict_panel(params, pb)
    gw_s, gV_s = fm_grad_panel(params, pb, pred, sorted_chunks=True)
    gw_u, gV_u = fm_grad_panel(params, pb, pred, sorted_chunks=False)
    np.testing.assert_allclose(np.asarray(gw_s), np.asarray(gw_u),
                               rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gV_s), np.asarray(gV_u),
                               rtol=2e-5, atol=1e-6)


# ------------------------------------------------ ISSUE 30: the head tier
# the first token of a lane's run is read straight into the lane's row,
# tokens 2.. are chunked, and the chunk cap follows the counted chunks
_TT_B, _TT_U = 48, 96


def _tt_panel(kind: str):
    """(flat lanes i32[B*F], flat values or None, F): a binary panel with
    zipf-skewed lanes (hot lanes' runs far longer than CHUNK_L, most
    lanes touched once or never), a valued one with zero-valued pad
    cells on lane 0, or one whose lanes are all touched at most once."""
    import numpy as np
    rng = np.random.RandomState(30)
    if kind == "binary":
        F = 7
        return (((rng.zipf(1.3, _TT_B * F) - 1) % _TT_U).astype(np.int32),
                None, F)
    if kind == "valued":
        F = 6
        flat = ((rng.zipf(1.2, _TT_B * F) - 1) % _TT_U).astype(np.int32)
        vals = rng.rand(_TT_B * F).astype(np.float32)
        pad = rng.rand(_TT_B * F) < 0.2          # ragged rows' pad cells
        flat[pad], vals[pad] = 0, 0.0
        return flat, vals, F
    assert kind == "singletons"
    return rng.permutation(_TT_U).astype(np.int32), None, 2


def _tt_build(builder: str, flat, vals, F, C, head):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from difacto_tpu.ops.batch import (CHUNK_L, panel_chunk_tokens_flat,
                                       panel_chunk_tokens_np)
    if builder == "np":
        return panel_chunk_tokens_np(flat, vals, _TT_U, _TT_B, F, C=C,
                                     head=head)
    out = jax.jit(panel_chunk_tokens_flat,
                  static_argnums=(2, 3, 4, 5, 6, 7))(
        jnp.asarray(flat), None if vals is None else jnp.asarray(vals),
        _TT_U, _TT_B, F, CHUNK_L, C, head)
    return tuple(None if x is None else np.asarray(x) for x in out)


@pytest.mark.parametrize("builder", ["np", "jit"])
@pytest.mark.parametrize("kind", ["binary", "valued", "singletons"])
@pytest.mark.parametrize("head", [True, False])
def test_two_tier_layout_invariants(builder, kind, head):
    """Both builders: every token's row id appears exactly once over the
    head and the chunk cells of its lane, chunk lanes ascend, used chunks
    are a prefix, pads point out of bounds, and the chunks used are the
    chunks counted: sum over lanes of ceil((len - 1) / L) with the head
    tier (none at all for lanes touched once), ceil(len / L) without."""
    import numpy as np
    from difacto_tpu.ops.batch import CHUNK_L, chunk_cap, chunks_needed
    flat, vals, F = _tt_panel(kind)
    B, U, L = len(flat) // F, _TT_U, CHUNK_L
    lens = np.bincount(flat, minlength=U)
    want = int(sum(-(-(n - head) // L) for n in lens if n > 0))
    need = chunks_needed(flat, U, head=head)
    assert need == want
    if kind == "singletons" and head:
        assert need == 0
    C = need + 3
    assert C <= chunk_cap(U, B * F)
    out = _tt_build(builder, flat, vals, F, C, head)
    assert len(out) == (5 if head else 3)
    ci, cl, cv = out[:3]
    assert ci.shape == (C, L) and cl.shape == (C,)
    assert ci.dtype == np.int32 and cl.dtype == np.int32
    used = cl < U
    assert used.sum() == need and used[:need].all()
    assert (np.diff(cl[used]) >= 0).all()
    assert (cl[~used] == U).all() and (ci[~used] == B).all()
    if head:
        hr, hv = out[3:]
        assert hr.shape == (U,) and hr.dtype == np.int32
        assert ((hr == B) == (lens == 0)).all()
    assert (cv is None) == (vals is None)
    rows = np.arange(B * F) // F
    for lane in range(U):
        cells = ci[cl == lane]
        got = cells[cells < B]
        gv = None if cv is None else cv[cl == lane][cells < B]
        if head and lens[lane]:
            got = np.append(got, hr[lane])
            gv = None if gv is None else np.append(gv, hv[lane])
        mine = flat == lane
        np.testing.assert_array_equal(np.sort(got), np.sort(rows[mine]))
        if gv is not None:
            # the values travel with their tokens
            np.testing.assert_allclose(np.sort(got * 2.0 + gv),
                                       np.sort(rows[mine] * 2.0
                                               + vals[mine]))
    # a cap the batch does not fit is an error on the host, never a
    # silently shorter layout
    if builder == "np" and need:
        with pytest.raises(ValueError, match="exceeds cap"):
            _tt_build("np", flat, vals, F, need - 1, head)


def _tt_grads(kind, V_dim, layout_of, sorted_chunks=True):
    """(unsorted scatter backward, chunked backward under the layout
    ``layout_of(flat, vals, F)``) of one panel and one parameter set."""
    import jax.numpy as jnp
    import numpy as np
    from difacto_tpu.losses import FMParams, fm_grad_panel, fm_predict_panel
    from difacto_tpu.ops.batch import PanelBatch
    flat, vals, F = _tt_panel(kind)
    B, U = len(flat) // F, _TT_U
    rng = np.random.RandomState(31)
    params = FMParams(
        w=jnp.asarray(rng.randn(U).astype(np.float32)),
        V=(jnp.asarray(rng.randn(U, V_dim).astype(np.float32) * 0.1)
           if V_dim else None),
        v_mask=(jnp.asarray((rng.rand(U) > 0.3).astype(np.float32))
                if V_dim else None))
    pb = PanelBatch(
        idx=jnp.asarray(flat.reshape(B, F)),
        vals=None if vals is None else jnp.asarray(vals.reshape(B, F)),
        labels=jnp.asarray(rng.choice([0.0, 1.0], B).astype(np.float32)),
        rweight=jnp.asarray(rng.rand(B).astype(np.float32)),
        row_mask=jnp.asarray((np.arange(B) < B - 5).astype(np.float32)),
        num_rows=jnp.asarray(B - 5, jnp.int32),
        num_uniq=jnp.asarray(U, jnp.int32))
    pred = fm_predict_panel(params, pb)
    plain = fm_grad_panel(params, pb, pred)
    chunked = fm_grad_panel(params, layout_of(pb, flat, vals, F), pred,
                            sorted_chunks=sorted_chunks)
    return plain, chunked


def _tt_close(plain, chunked, V_dim):
    import numpy as np
    np.testing.assert_allclose(np.asarray(plain[0]), np.asarray(chunked[0]),
                               rtol=2e-5, atol=1e-6)
    if V_dim:
        np.testing.assert_allclose(np.asarray(plain[1]),
                                   np.asarray(chunked[1]),
                                   rtol=2e-5, atol=1e-6)
    else:
        assert plain[1] is None and chunked[1] is None


@pytest.mark.parametrize("builder", ["np", "jit"])
@pytest.mark.parametrize("kind", ["binary", "valued"])
@pytest.mark.parametrize("V_dim", [6, 0])
@pytest.mark.parametrize("sorted_chunks", [True, False])
def test_two_tier_backward_matches_unsorted(builder, kind, V_dim,
                                            sorted_chunks):
    """Head rows + chunked rest == the unsorted scatter backward, to
    float32 rounding: the same terms added in another order."""
    import jax.numpy as jnp
    from difacto_tpu.ops.batch import chunks_needed

    def layout_of(pb, flat, vals, F):
        C = chunks_needed(flat, _TT_U) + 2
        out = _tt_build(builder, flat, vals, F, C, True)
        pbc = pb.with_chunks(tuple(None if x is None else jnp.asarray(x)
                                   for x in out))
        assert pbc.head_row is not None
        assert (pbc.head_vals is None) == (vals is None)
        return pbc

    _tt_close(*_tt_grads(kind, V_dim, layout_of, sorted_chunks), V_dim)


@pytest.mark.parametrize("kind", ["binary", "valued"])
@pytest.mark.parametrize("V_dim", [6, 0])
def test_headless_panel_batch_is_todays_layout(kind, V_dim):
    """The contract the benchmark's compile test rests on:
    ``panel_chunk_tokens_flat`` with five positional arguments returns a
    3-tuple at the static ``chunk_cap``, and a PanelBatch built with
    chunk_idx, chunk_lane, chunk_vals and NO head arrays gives the same
    gradients through the same backward."""
    from difacto_tpu.ops.batch import (CHUNK_L, PanelBatch, chunk_cap,
                                       panel_chunk_tokens_flat)
    assert PanelBatch._field_defaults["head_row"] is None
    assert PanelBatch._field_defaults["head_vals"] is None

    def layout_of(pb, flat, vals, F):
        import jax.numpy as jnp
        out = panel_chunk_tokens_flat(
            jnp.asarray(flat), None if vals is None else jnp.asarray(vals),
            _TT_U, _TT_B, F)
        assert len(out) == 3
        ci, cl, cv = out
        assert ci.shape == (chunk_cap(_TT_U, len(flat)), CHUNK_L)
        pbc = pb._replace(chunk_idx=ci, chunk_lane=cl, chunk_vals=cv)
        assert pbc.head_row is None and pbc.head_vals is None
        return pbc

    _tt_close(*_tt_grads(kind, V_dim, layout_of), V_dim)


def test_two_tier_terms_are_float32():
    """No term is narrowed: the head rows and the partials come from one
    float32 gather of the float32 ``row_q``, whatever the table's
    dtype."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from difacto_tpu.losses import FMParams
    from difacto_tpu.losses.fm import _fm_grad_panel_chunked
    from difacto_tpu.ops.batch import (PanelBatch, chunks_needed,
                                       panel_chunk_tokens_np)
    flat, _, F = _tt_panel("binary")
    B, U, k = len(flat) // F, _TT_U, 4
    out = panel_chunk_tokens_np(flat, None, U, B, F,
                                C=chunks_needed(flat, U), head=True)
    z = jnp.zeros((B,), jnp.float32)
    pb = PanelBatch(idx=jnp.asarray(flat.reshape(B, F)), vals=None,
                    labels=z, rweight=z, row_mask=z,
                    num_rows=jnp.asarray(B, jnp.int32),
                    num_uniq=jnp.asarray(U, jnp.int32)).with_chunks(
        tuple(None if x is None else jnp.asarray(x) for x in out))
    params = FMParams(w=jnp.zeros((U,), jnp.bfloat16),
                      V=jnp.zeros((U, k), jnp.bfloat16),
                      v_mask=jnp.ones((U,), jnp.float32))
    jaxpr = jax.make_jaxpr(
        lambda p, xv: _fm_grad_panel_chunked(params, pb, p, xv))(
        z, jnp.zeros((B, k), jnp.float32))
    gathers = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "gather"]
    scatters = [e for e in jaxpr.jaxpr.eqns
                if e.primitive.name == "scatter-add"]
    # one gather reads the row quantities for both tiers: the chunk cells
    # and, behind them, the head rows in rows of L
    assert len(gathers) == 1 and len(scatters) == 1
    for e in gathers + scatters:
        assert all(v.aval.dtype == np.float32 for v in e.outvars)
    C, L = out[0].shape
    assert U % L == 0
    assert gathers[0].outvars[0].aval.shape == (C + U // L, L, k + 1)
    # ... from B rows and one of zeros, which every pad is clipped onto
    assert gathers[0].invars[0].aval.shape == (B + 1, k + 1)
    # the partials are added INTO the gathered head rows [U, k+1]
    assert scatters[0].invars[0].aval.shape == (U, k + 1)
    assert scatters[0].invars[2].aval.shape == (C, k + 1)


# ------------------------------- ISSUE 36: element idx through the row gather
# the flat model's per-token gathers (w[idx] in the panel forward, p[idx]
# in the chunked backward) read rows of 128 lanes in slabs and select one
def _bits(x):
    """float32 values as their bits, the sign of a zero aside."""
    import numpy as np
    x = np.asarray(x, np.float32)
    return np.where(x == 0, np.float32(0), x).view(np.uint32)


@pytest.mark.parametrize("site", ["forward", "backward"])
@pytest.mark.parametrize("count", ["under", "at", "over"])
@pytest.mark.parametrize("n", [1, 127, 128, 65537])
def test_take_lanes_is_the_plain_gather_bit_for_bit(monkeypatch, n, count,
                                                    site):
    """``_take_lanes`` against the expression it stands for at each of
    its two sites, over vectors that are and are not whole rows of 128
    lanes, index lists under, at and over one slab (a small slab patched
    in), indices at both ends and out of range on both sides, and a NaN
    and an Inf in lanes of a selected row that no index selects."""
    import jax.numpy as jnp
    import numpy as np
    from difacto_tpu.losses import fm
    slab = 64
    monkeypatch.setattr(fm, "_LANE_SLAB", slab)
    rng = np.random.RandomState(n)
    vec = rng.randn(n).astype(np.float32)
    vec[rng.rand(n) < 0.3] = 0.0
    vec[::7] *= -1                      # -0.0 among the zeros
    ends = [0, n - 1, -1, -n, -n - 3, n, n + 5, 2 ** 31 - 1, -2 ** 31]
    allowed = np.arange(n)
    if n >= 127:
        vec[5], vec[6] = np.nan, np.inf
        allowed = allowed[(allowed != 5) & (allowed != 6)]
        ends += [4, 7]                  # their neighbours, the same row
    many = {"under": slab - 7, "at": slab, "over": 3 * slab + 9}[count]
    many = max(many, len(ends))
    idx = rng.choice(allowed, many).astype(np.int32)
    idx[:len(ends)] = ends
    if n >= 127:                        # -1 -> n-1, n.. -> n-1: never 5, 6
        assert not np.isin(np.clip(np.where(idx < 0, idx + n, idx),
                                   0, n - 1), [5, 6]).any()
    vec_d = jnp.asarray(vec)
    if site == "forward":               # params.w[pb.idx], [B, F]
        idx = idx[: many - many % 3].reshape(-1, 3)
        want = vec_d[jnp.asarray(idx)]
        got = fm._take_lanes(vec_d, jnp.asarray(idx))
    else:                               # pad(p).at[idx].get(clip), [C', L]
        idx = idx[: many - many % 4].reshape(-1, 4)
        want = jnp.pad(vec_d, (0, 1)).at[jnp.asarray(idx)].get(mode="clip")
        got = fm._take_lanes(jnp.pad(vec_d, (0, 1)), jnp.asarray(idx))
    assert got.shape == idx.shape and got.dtype == jnp.float32
    np.testing.assert_array_equal(_bits(got), _bits(want))
    assert np.isfinite(np.asarray(got)).all()
    # under one slab the program has no loop; over it, one
    import jax
    text = str(jax.make_jaxpr(fm._take_lanes)(vec_d, jnp.asarray(idx)))
    assert ("scan[" in text) == (idx.size > slab)


@pytest.mark.parametrize("slabs", ["one", "many"])
@pytest.mark.parametrize("head", [True, False])
@pytest.mark.parametrize("kind", ["binary", "valued"])
def test_flat_panel_sites_match_coo(monkeypatch, kind, head, slabs):
    """The two sites end to end: the flat (``V = None``) panel forward
    and chunked backward, with and without the head tier and values, in
    one slab and in many, against ``fm_predict`` / ``fm_grad`` on the COO
    batch of the same rows."""
    import jax.numpy as jnp
    import numpy as np
    from difacto_tpu.data.rowblock import RowBlock
    from difacto_tpu.losses import (FMParams, fm, fm_grad, fm_grad_panel,
                                    fm_predict, fm_predict_panel)
    from difacto_tpu.ops.batch import (chunks_needed, pad_batch, pad_panel,
                                       panel_chunk_tokens_np)
    if slabs == "many":
        monkeypatch.setattr(fm, "_LANE_SLAB", 32)
    flat, vals, F = _tt_panel(kind)
    B, U = len(flat) // F, _TT_U
    rng = np.random.RandomState(36)
    blk = RowBlock(
        offset=np.arange(B + 1, dtype=np.int64) * F,
        label=rng.choice([0.0, 1.0], B).astype(np.float32),
        index=flat.astype(np.uint32), value=vals,
        weight=rng.rand(B).astype(np.float32))
    params = FMParams(w=jnp.asarray(rng.randn(U).astype(np.float32)))
    coo = pad_batch(blk, num_uniq=U, batch_cap=B)
    layout = panel_chunk_tokens_np(
        flat, vals, U, B, F, C=chunks_needed(flat, U, head=head) + 2,
        head=head)
    pb = pad_panel(blk, U, B, F).with_chunks(
        tuple(None if x is None else jnp.asarray(x) for x in layout))
    assert (pb.head_row is not None) == head
    assert (pb.vals is not None) == (vals is not None)
    if slabs == "many":
        assert pb.idx.size > 32 and pb.chunk_idx.size > 32
    pred_c = fm_predict(params, coo)
    pred_p = fm_predict_panel(params, pb)
    np.testing.assert_allclose(np.asarray(pred_p), np.asarray(pred_c),
                               rtol=1e-5, atol=1e-6)
    gw_c, gV_c = fm_grad(params, coo, pred_c)
    gw_p, gV_p = fm_grad_panel(params, pb, pred_c)
    assert gV_c is None and gV_p is None
    np.testing.assert_allclose(np.asarray(gw_p), np.asarray(gw_c),
                               rtol=5e-5, atol=1e-6)
    # and in one slab or many the values are the same to the bit
    if slabs == "many":
        monkeypatch.setattr(fm, "_LANE_SLAB", 1 << 20)
        np.testing.assert_array_equal(
            _bits(fm_predict_panel(params, pb)), _bits(pred_p))
        np.testing.assert_array_equal(
            _bits(fm_grad_panel(params, pb, pred_c)[0]), _bits(gw_p))
