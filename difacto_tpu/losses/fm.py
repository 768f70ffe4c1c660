"""Factorization-machine and logistic losses as pure jit kernels.

Re-derivation of the reference's FMLoss (src/loss/fm_loss.h) in gathered-row
form. The loss receives the batch's *already-gathered* parameter rows — w[U]
and V[U, k] for the batch's U distinct features — mirroring the reference
contract where the loss consumes pulled weight vectors, but with the
variable-length [w, V...] byte layout (fm_loss.h:51-53, sgd_learner.cc:151-165)
replaced by fixed (U,) + (U, k) arrays plus an activation mask ``v_mask``
(1.0 where the reference would have V_pos >= 0, i.e. the embedding exists and
is not l1-shrunk away).

Forward (fm_loss.h:43,67-119):
    pred = X w + 0.5 * sum((X V)^2 - (X.X)(V.V), axis=1), clamped to [-20, 20]

Backward (fm_loss.h:124-126,148-203), with p = -y / (1 + exp(y pred)) * rw:
    gw = X' p
    gV = X' diag(p) X V - diag((X.X)' p) V        (masked by v_mask)

Logistic loss (src/loss/logit_loss.h) is the V_dim=0 special case — same code
path with V=None.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import fused
from ..ops.batch import DeviceBatch
from ..ops.segment import spmm, spmm_t, spmv, spmv_t

PRED_CLAMP = 20.0

# widest panel that takes the unrolled column-loop forward; wider panels
# use the single [B,F]-cell gather (trace size is linear in width for
# the loop, constant for the big gather)
_COLLOOP_MAX_WIDTH = 64

# cells a slab of _take_lanes gathers at once: 65,536 rows of 128 float32
# lanes are 32 MiB, which the v5e's compiler keeps in fast memory from the
# gather to the lane sum (the slab's rows never reach HBM); all cells of
# a 65,536 x 39 batch at once would be 1.9 GB of rows written and read
# back. Measured on a v5e, the flat forward + backward of that batch
# alone: 17.80 ms at 32,768 cells a slab, 17.71 at 65,536, 18.07 at
# 131,072, 21.37 at 262,144, 19.30 with no slabs (47.57 for the
# one-dimensional gathers)
_LANE_SLAB = 65536

# trips of the column loop of the 8-bit rows' forward, ceil(F /
# _CODE_TRIPS) columns unrolled to a trip. At the 8-bit cell's shapes (39
# columns) on a v5e: unrolled whole, forward 10.49 ms a step and a pair
# program of 41.2 MB, which each run loads 2 s longer than the float32
# source's 25.0 MB; three trips 10.29 ms and 23.6 MB; one column a trip
# 26.46 ms (its sums are carried in a padded layout through HBM)
_CODE_TRIPS = 3


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["src"], meta_fields=["kind"])
@dataclasses.dataclass(frozen=True)
class CodeRows:
    """The panel forward's gather source of 8-bit rows (:func:`code_rows`):
    ``src`` uint16[U, ceil(k/2) + 4], ``kind`` the codes' form ("int8"
    or "fp8", ops/fused.quant_half)."""
    src: jnp.ndarray
    kind: str


class FMParams(NamedTuple):
    """Gathered per-batch parameter rows."""
    w: jnp.ndarray                     # f32[U]
    V: Optional[jnp.ndarray] = None    # f32[U, k] or None (pure LR)
    v_mask: Optional[jnp.ndarray] = None  # f32[U]; None == all active
    # 8-bit rows: the forward gathers these codes in place of [w | V]
    codes: Optional[CodeRows] = None


def _vmask(params: FMParams) -> jnp.ndarray:
    if params.v_mask is None:
        return jnp.ones_like(params.w)
    return params.v_mask


def fm_predict_xv(params: FMParams, batch: DeviceBatch
                  ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """(pred[B], XV[B,k] or None); padding rows produce garbage — mask at
    use sites. XV is handed to the backward so the fused train step never
    recomputes the X·V SpMM (round-4 profile: the backward's duplicate
    token gather was ~15% of the step)."""
    B = batch.batch_cap
    pred = spmv(batch.vals, batch.rows, batch.cols, params.w, B)
    XV = None
    if params.V is not None and params.V.shape[1] > 0:
        Vm = params.V * _vmask(params)[:, None]
        XV = spmm(batch.vals, batch.rows, batch.cols, Vm, B)
        XXVV = spmm(batch.vals ** 2, batch.rows, batch.cols, Vm ** 2, B)
        pred = pred + 0.5 * jnp.sum(XV ** 2 - XXVV, axis=1)
    return jnp.clip(pred, -PRED_CLAMP, PRED_CLAMP), XV


def fm_predict(params: FMParams, batch: DeviceBatch) -> jnp.ndarray:
    return fm_predict_xv(params, batch)[0]


def _p_vector(pred: jnp.ndarray, batch: DeviceBatch) -> jnp.ndarray:
    """p = -y/(1+exp(y*pred)) * row_weight, zeroed on padding rows."""
    y = jnp.where(batch.labels > 0, 1.0, -1.0)
    p = -y / (1.0 + jnp.exp(y * pred))
    return p * batch.rweight * batch.row_mask


def fm_grad(params: FMParams, batch: DeviceBatch, pred: jnp.ndarray,
            xv: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Returns (gw[U], gV[U,k] or None). ``xv`` is the forward's X·V
    (fm_predict_xv); None recomputes it."""
    U = params.w.shape[0]
    p = _p_vector(pred, batch)
    gw = spmv_t(batch.vals, batch.rows, batch.cols, p, U)
    if params.V is None or params.V.shape[1] == 0:
        return gw, None
    vm = _vmask(params)
    Vm = params.V * vm[:, None]
    XV = xv if xv is not None else spmm(batch.vals, batch.rows, batch.cols,
                                        Vm, batch.batch_cap)
    # X' diag(p) X V
    t1 = spmm_t(batch.vals, batch.rows, batch.cols, p[:, None] * XV, U)
    # diag((X.X)'p) V
    xxp = spmv_t(batch.vals ** 2, batch.rows, batch.cols, p, U)
    gV = (t1 - xxp[:, None] * Vm) * vm[:, None]
    return gw, gV


def _take_lanes(vec: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``vec[idx]`` of a float32 vector through the row gather: bit for
    bit ``vec.at[idx].get(mode="clip")`` (a negative index wraps once,
    what is still out of range lands on the nearest end), but that -0.0
    may read +0.0.

    A one-dimensional gather is a loop over scalars on the TPU (measured
    on a v5e: 7.1 ns an element with operand and result both in fast
    memory), where a gather of whole rows pays 1.5 ns a row. So the
    vector is viewed as rows of 128 lanes, element i is lane ``i & 127``
    of row ``i >> 7``, and a select keeps that lane (a select, not a
    multiply by a one-hot: an unselected NaN or Inf must not leak) before
    the lanes are summed: one value and 127 zeros. The cells go
    ``_LANE_SLAB`` at a time, one slab after another, so that one slab's
    gathered rows are live at a time; a list no longer than a slab is one
    gather with no loop."""
    n = vec.shape[0]
    rows = jnp.pad(vec, (0, -n % 128)).reshape(-1, 128)
    cells = idx.reshape(-1)
    cells = jnp.clip(jnp.where(cells < 0, cells + n, cells), 0, n - 1)

    def slab(i):
        picked = jnp.where(
            (i & 127)[:, None] == jnp.arange(128, dtype=i.dtype),
            rows[i >> 7], 0)
        return jnp.sum(picked, axis=1)

    count = cells.shape[0]
    if count <= _LANE_SLAB:
        return slab(cells).reshape(idx.shape)
    cells = jnp.pad(cells, (0, -count % _LANE_SLAB))
    out = jax.lax.map(slab, cells.reshape(-1, _LANE_SLAB))
    return out.reshape(-1)[:count].reshape(idx.shape)


def packs_forward(dtype, k: int) -> bool:
    """Whether the panel forward carries its ``[w | V]`` gather source of
    ``k + 1`` lanes as two 16-bit halves of its bits (``_row_taker``):
    float32 storage whose packed row of ``2(k + 1)`` halves still fits one
    128-lane row. Such a row pads to 256 B where the float32 row pads to
    512 B, and at the cells' row cap (294,912 rows) the v5e's compiler
    keeps the packed source in fast memory (``S(1)``, 75.5 MB, as it
    keeps bf16 ``[w | V64]``) and the float32 one in HBM (151 MB): a
    row gather costs ~10 ns a row from HBM and 1.5-1.8 from fast memory
    (measured on a v5e: V16's forward 27.96 -> 8.93 ms a step of 39 x
    65,536 tokens). bfloat16 rows are 2 B a lane already; float32 at
    ``k >= 64`` pads to the same 512 B either way."""
    return k > 0 and jnp.dtype(dtype) == jnp.float32 and 2 * (k + 1) <= 128


def _row_taker(wv: jnp.ndarray):
    """``idx -> wv[idx]``, bit for bit (-0.0, NaN payloads, infinities
    and subnormals included). Where ``packs_forward`` holds, the rows are
    gathered from one ``uint16[U, 2n]`` array, the high halves of the
    float32 bits in lanes ``0..n-1`` and the low halves in ``n..2n-1``,
    and reassembled after the gather. Same-width bitcasts and shifts
    only: a ``[U, n, 2]`` view would pad its minor 2 to 128 lanes."""
    n = wv.shape[1]
    if not packs_forward(wv.dtype, n - 1):
        return lambda idx: wv[idx]
    bits = jax.lax.bitcast_convert_type(wv, jnp.uint32)
    src = jnp.concatenate([(bits >> 16).astype(jnp.uint16),
                           (bits & 0xFFFF).astype(jnp.uint16)], axis=1)

    def take(idx):
        half = src[idx].astype(jnp.uint32)
        return jax.lax.bitcast_convert_type(
            (half[..., :n] << 16) | half[..., n:], jnp.float32)
    return take


def packs_codes(k: int) -> bool:
    """Whether the panel forward of 8-bit rows (slot_dtype int8 or fp8)
    gathers their codes (:func:`code_rows`): ``k`` one-byte codes two to
    a 16-bit lane and the four halves of ``w`` and the V scale fit one
    128-lane row. At ``V_dim = 64`` that is ``u16[U, 36]``, 256 B a
    padded row, the bytes of bf16 ``[w | V64]``, which the v5e's
    compiler keeps in fast memory at the cells' row cap, where the
    dequantised float32 ``[U, 65]`` source (512 B a padded row) sits in
    HBM: 11.7 ns a gathered row against ~3.6 (PERF.md 5)."""
    return k > 0 and (k + 1) // 2 + 4 <= 128


def _halves(x: jnp.ndarray):
    """float32[U] -> (high, low) 16-bit halves of its bits, u16[U, 1]."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)[:, None]
    return (bits >> 16).astype(jnp.uint16), (bits & 0xFFFF).astype(jnp.uint16)


def code_rows(codes: jnp.ndarray, w: jnp.ndarray, scale: jnp.ndarray,
              v_mask: jnp.ndarray, kind: str) -> CodeRows:
    """The forward's gather source of 8-bit rows: ``codes`` int8[U, k]
    (the row's V codes as stored), ``w`` and the V ``scale`` float32[U],
    ``v_mask`` 0/1 float32[U]. Lane j of the first ``m = ceil(k/2)``
    holds code j in its low byte and code ``j + m`` in its high byte;
    then the high halves of ``w`` and of ``scale * v_mask``, then their
    low halves. The mask folds into the scale bit for bit: a scale is
    positive, so ``(c * s) * 0`` and ``c * (s * 0)`` are the same signed
    zero. Same-width bitcasts, shifts and 32-bit integers only: no minor
    dimension of 2 to pad to 128 lanes, no 8-bit type to repack."""
    k = codes.shape[1]
    m = (k + 1) // 2
    c = jnp.pad(codes.astype(jnp.int32) & 0xFF, ((0, 0), (0, 2 * m - k)))
    w_hi, w_lo = _halves(w)
    s_hi, s_lo = _halves(scale * v_mask)
    return CodeRows(jnp.concatenate(
        [(c[:, :m] | (c[:, m:] << 8)).astype(jnp.uint16),
         w_hi, s_hi, w_lo, s_lo], axis=1), kind)


def _code_taker(cr: CodeRows, k: int):
    """``idx -> [w | V * v_mask][idx]`` from :func:`code_rows`' source,
    bit for bit the dequantised float32 rows' gather: each gathered row's
    codes are widened and scaled by ``ops/fused.dequant_half``, with the
    mask riding in the scale. The unpacking is 32-bit arithmetic on whole
    columns (fp8 codes alone pass through 8 bits, for their float8
    bitcast): with 8-bit and two-lane values the v5e's compiler repacks
    them in copies of their own, 0.6 MB more program a gathered column
    and 3.5 s more set-up a run loading it from the compile cache."""
    m = (k + 1) // 2

    def take(idx):
        x = cr.src[idx].reshape(-1, m + 4).astype(jnp.uint32)

        def f32(hi, lo):
            return jax.lax.bitcast_convert_type(
                (x[:, hi] << 16) | x[:, lo], jnp.float32)

        c = jnp.concatenate([x[:, :m] & 0xFF, x[:, :m] >> 8],
                            axis=1)[:, :k].astype(jnp.int32)
        if cr.kind == "int8":
            codes = (c ^ 0x80) - 0x80                    # sign-extended
        else:
            codes = jax.lax.bitcast_convert_type(c.astype(jnp.uint8),
                                                 jnp.int8)
        V = fused.dequant_half(codes, f32(m + 1, m + 3), cr.kind)
        tok = jnp.concatenate([f32(m, m + 2)[:, None], V], axis=1)
        return tok.reshape(*idx.shape, k + 1)
    return take


def fm_predict_panel_xv(params: FMParams, pb
                        ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Panel-layout forward (ops/batch.py PanelBatch): one [B]-row gather
    of combined [w | V] rows PER PANEL COLUMN, accumulated into f32
    running sums — no COO segment machinery. Same arithmetic as
    fm_predict (fm_loss.h:43,67-119). Returns (pred, XV) so the backward
    can skip the duplicate token gather.

    The column loop (vs one [B,F]-cell gather) keeps each per-column
    token block VMEM-resident: the single big gather made XLA materialize
    the [B*F, 1+k] token stream to HBM plus a layout reshape (~10 ms of a
    39 ms step at bench shapes, traced); the unrolled loop measures
    37.8 ms vs 39.4. Panels wider than
    _COLLOOP_MAX_WIDTH fall back to the single-gather form — the loop
    unrolls one gather per column into the jit trace, so program size
    and compile time grow linearly with width."""
    if params.V is None or params.V.shape[1] == 0:
        wc = _take_lanes(params.w, pb.idx)          # [B, F]
        if pb.vals is not None:
            wc = wc * pb.vals
        return jnp.clip(jnp.sum(wc, axis=1), -PRED_CLAMP, PRED_CLAMP), None
    # the [U, 1+k] combined rows keep V's STORAGE dtype: with bf16 V_dtype
    # the per-token gather (the step's largest stream at big batches)
    # moves half the bytes; accumulation is f32 below. Narrow float32 rows
    # are gathered as two 16-bit halves (packs_forward) and reassembled,
    # 8-bit rows as their codes (code_rows) and dequantised after
    dt = params.V.dtype
    k = params.V.shape[1]
    B, F = pb.idx.shape
    if params.codes is not None:
        take = _code_taker(params.codes, k)
    else:
        Vm = params.V * _vmask(params).astype(dt)[:, None]
        take = _row_taker(
            jnp.concatenate([params.w.astype(dt)[:, None], Vm], axis=1))
    if F > _COLLOOP_MAX_WIDTH:
        tok = take(pb.idx)                           # [B, F, 1+k]
        wc, t = tok[:, :, 0].astype(jnp.float32), tok[:, :, 1:]
        if pb.vals is not None:
            wc = wc * pb.vals
            t = t * pb.vals[:, :, None].astype(dt)   # t = val * V
        t = t.astype(jnp.float32)
        pred = jnp.sum(wc, axis=1)
        XV = jnp.sum(t, axis=1)
        XXVV = jnp.sum(t * t, axis=1)
    else:
        idxT = pb.idx.T                              # [F, B]

        def column(f, acc):
            pred, XV, XXVV = acc
            tok = take(idxT[f])                      # [B, 1+k]
            wc = tok[:, 0].astype(jnp.float32)
            t = tok[:, 1:]
            if pb.vals is not None:
                wc = wc * pb.vals[:, f]
                t = t * pb.vals[:, f, None].astype(dt)  # t = val * V
            t = t.astype(jnp.float32)
            return pred + wc, XV + t, XXVV + t * t

        acc = (jnp.zeros((B,), jnp.float32), jnp.zeros((B, k), jnp.float32),
               jnp.zeros((B, k), jnp.float32))
        if params.codes is not None:
            # the codes' unpacking is a dozen operations a column: F
            # copies of it grow the step programs by megabytes, which
            # every run loads from the compile cache before its window
            # opens. A loop of _CODE_TRIPS trips keeps a third of them,
            # the same sums in the same order
            acc = jax.lax.fori_loop(0, F, column, acc,
                                    unroll=-(-F // _CODE_TRIPS))
        else:
            for f in range(F):
                acc = column(f, acc)
        pred, XV, XXVV = acc
    pred = pred + 0.5 * jnp.sum(XV * XV - XXVV, axis=1)
    return jnp.clip(pred, -PRED_CLAMP, PRED_CLAMP), XV


def fm_predict_panel(params: FMParams, pb) -> jnp.ndarray:
    return fm_predict_panel_xv(params, pb)[0]


def _fm_grad_panel_chunked(params: FMParams, pb, p: jnp.ndarray,
                           XV: Optional[jnp.ndarray],
                           sorted_chunks: bool = True
                           ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Chunked-run backward (pb.chunk_* present, ops/batch.py
    panel_chunk_tokens): the fastest variant. The sorted scatter-add is a
    serial per-token update loop (~10 ns/row — half the fused step at
    bench shapes, the round-4 trace's fusion.9); here the per-lane sums
    are computed as a dense vectorised gather+reduce over fixed-L chunks
    of each lane's token run, and the scatter shrinks to one partial row
    a chunk.

    Two tiers when the batch carries ``head_row``: lane u's first token
    is gathered straight into row u (no chunk, no partial: a lane touched
    once costs one gathered row), only tokens 2.. of a run are chunked,
    and the partials are scatter-added INTO the gathered head rows. A
    batch without head arrays has every token in a chunk and the partials
    land in zeros. The same float32 terms either way, added in another
    order.

    ONE gather serves both tiers: the head rows ride behind the chunk
    cells as ceil(U/L) more rows of L indices, so the row quantities are
    read once, from wherever the compiler keeps them (measured on a v5e:
    a second, separate gather of the U head rows read them from HBM at
    10 ns a row, 3.0 ms a step, where the chunk gather pays 1.8). The
    quantities carry one zero row at the end and every index is clipped
    onto it: padded chunk cells and the heads of untouched lanes point
    at row b_cap or beyond and so read zeros, with no mask to apply
    after the gather. Padded chunks carry lane u_cap (out of bounds ->
    dropped).

    ``sorted_chunks`` declares chunk_lane globally ascending — true for
    host-local/single-shard layouts, FALSE for dp-sharded mesh batches
    (each shard's block is sorted but the concatenation is not; lying to
    XLA's scatter lowering would be undefined behavior)."""
    U = params.w.shape[0]
    C, L = pb.chunk_idx.shape
    idx, vals = pb.chunk_idx, pb.chunk_vals
    if pb.head_row is not None:
        def behind(cells, heads, fill):
            # the heads as rows of L behind the chunk cells
            heads = jnp.pad(heads, (0, -U % L), constant_values=fill)
            return jnp.concatenate([cells, heads.reshape(-1, L)])
        idx = behind(idx, pb.head_row, p.shape[0])
        if vals is not None:
            vals = behind(vals, pb.head_vals, 0)

    def lane_sums(*terms):
        """Per-cell terms [C (+ ceil(U/L)), L, n_i] -> the lanes' sums
        [U, sum n_i]: the chunks' partial sums scatter-added into the
        lanes' own (head) rows, or into zeros without the head tier."""
        partial = jnp.concatenate([jnp.sum(t, axis=1)[:C] for t in terms],
                                  axis=1)
        if pb.head_row is None:
            own = jnp.zeros((U, partial.shape[1]), jnp.float32)
        else:
            own = jnp.concatenate(
                [t[C:].reshape(-1, t.shape[-1])[:U] for t in terms], axis=1)
        return own.at[pb.chunk_lane].add(
            partial, indices_are_sorted=sorted_chunks, mode="drop")

    if params.V is None or params.V.shape[1] == 0:
        toks = _take_lanes(jnp.pad(p, (0, 1)), idx)  # [C', L]
        if vals is not None:
            toks = toks * vals
        return lane_sums(toks[:, :, None])[:, 0], None
    k = params.V.shape[1]
    vm = _vmask(params)
    Vm = (params.V * vm.astype(params.V.dtype)[:, None]).astype(jnp.float32)
    row_q = jnp.concatenate([p[:, None] * XV, p[:, None]], axis=1)  # [B,k+1]
    toks = jnp.pad(row_q, ((0, 1), (0, 0))).at[idx].get(
        mode="clip")                                       # [C', L, k+1]
    if vals is None:
        # binary panel: gw == xxp (x == x^2), k+1 columns serve both
        red = lane_sums(toks)
        t1, gw = red[:, :k], red[:, k]
        xxp = gw
    else:
        v = vals[:, :, None]                               # [C', L, 1]
        red = lane_sums(toks * v,                          # t1 | gw (x v)
                        toks[:, :, k:] * (v * v))          # xxp   (x v^2)
        t1, gw, xxp = red[:, :k], red[:, k], red[:, k + 1]
    gV = (t1 - xxp[:, None] * Vm) * vm[:, None]
    return gw, gV


def fm_grad_panel(params: FMParams, pb, pred: jnp.ndarray,
                  xv: Optional[jnp.ndarray] = None,
                  sorted_chunks: bool = True
                  ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Panel-layout backward: per-cell contributions are pure BROADCASTS
    of row quantities (p, p*XV), merged by ONE combined segment reduction
    [B*F, k+2] -> [U, k+2] for (t1 | gw | xxp). Same math as fm_grad
    (fm_loss.h:124-126,148-203). ``xv`` is the forward's X·V
    (fm_predict_panel_xv); None re-gathers the tokens to rebuild it.
    Batches carrying a chunked-run layout (panel_chunk_tokens) take the
    chunked fast path."""
    U = params.w.shape[0]
    B, F = pb.idx.shape
    p = _p_vector(pred, pb)                          # [B]
    if pb.chunk_lane is not None:
        if params.V is not None and params.V.shape[1] > 0 and xv is None:
            _, xv = fm_predict_panel_xv(params, pb)
        return _fm_grad_panel_chunked(params, pb, p, xv, sorted_chunks)
    flat_idx = pb.idx.reshape(B * F)
    if params.V is None or params.V.shape[1] == 0:
        cell = jnp.broadcast_to(p[:, None], (B, F))
        if pb.vals is not None:
            cell = cell * pb.vals
        gw = jax.ops.segment_sum(cell.reshape(B * F), flat_idx,
                                 num_segments=U)
        return gw, None
    k = params.V.shape[1]
    vm = _vmask(params)
    Vm = (params.V * vm.astype(params.V.dtype)[:, None])
    if xv is not None:
        XV = xv
    else:
        t = Vm[pb.idx]
        if pb.vals is not None:
            t = t * pb.vals[:, :, None].astype(t.dtype)
        XV = jnp.sum(t.astype(jnp.float32), axis=1)
    Vm = Vm.astype(jnp.float32)
    pXV = p[:, None] * XV                            # [B, k]
    contrib = jnp.concatenate([
        jnp.broadcast_to(pXV[:, None, :], (B, F, k)),
        jnp.broadcast_to(p[:, None, None], (B, F, 1)),   # -> gw
        jnp.broadcast_to(p[:, None, None], (B, F, 1)),   # -> xxp
    ], axis=2)
    if pb.vals is not None:
        v3 = pb.vals[:, :, None]
        contrib = contrib * jnp.concatenate(
            [jnp.broadcast_to(v3, (B, F, k + 1)), v3 * v3], axis=2)
    # the [B*F, k+2] contribution stream rides the storage dtype (bf16
    # when V_dtype is bf16: per-cell rounding only); accumulation into the
    # per-feature sums stays float32 via the scatter-add's output buffer
    red = jnp.zeros((U, k + 2), jnp.float32).at[flat_idx].add(
        contrib.astype(params.V.dtype).reshape(B * F, k + 2))
    t1, gw, xxp = red[:, :k], red[:, k], red[:, k + 1]
    gV = (t1 - xxp[:, None] * Vm) * vm[:, None]
    return gw, gV


def logit_objv(pred: jnp.ndarray, batch: DeviceBatch) -> jnp.ndarray:
    """sum log(1 + exp(-y*pred)) over real rows (include/difacto/loss.h:57-66).

    Not averaged — the reference accumulates raw sums and lets the progress
    printer divide (sgd_utils.h:100-109)."""
    y = jnp.where(batch.labels > 0, 1.0, -1.0)
    per_row = jnp.log1p(jnp.exp(-y * pred))
    return jnp.sum(per_row * batch.row_mask)
