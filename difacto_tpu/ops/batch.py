"""Device batch representation: padded COO segments.

The bridge between the host CSR pipeline and XLA's static-shape world. A
localized row block (data/localizer.py) becomes a :class:`DeviceBatch` of
fixed-bucket-size arrays:

- ``rows[NNZ]`` int32 segment ids, ``cols[NNZ]`` int32 local feature slots,
  ``vals[NNZ]`` float32 (zero on padding — padded entries contribute nothing
  to any segment sum);
- ``labels/rweight/row_mask [B]`` per-row arrays.

Bucketing pads NNZ, U (distinct features) and B (rows) up to the next
power-of-two-ish bucket so jit recompiles only per bucket, not per batch —
this is the TPU answer to the reference's fully dynamic per-batch shapes
(its SArray messages can be any length; XLA cannot).

The reference analog of this file is the implicit contract between
Localizer's compact CSR and the SpMV/SpMM kernels (src/common/spmv.h:16-40).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp
import numpy as np

from ..base import REAL_DTYPE
from ..data.rowblock import RowBlock
from ..obs import names


class DeviceBatch(NamedTuple):
    """Padded COO batch; all leaves are jnp arrays, shapes static per bucket.

    ``cols`` address the batch's sorted-unique slot vector directly: every
    producer resolves in-batch collisions on the HOST (store.map_keys_dedup
    or the producer-thread np.unique), rewriting the O(nnz) index array
    once per batch. A device-side remap permutation used to carry this for
    the cached reader; it cost an unsorted u_cap-row permute + scatter-add
    per step — more than the host gather it saved.
    """
    rows: jnp.ndarray      # int32[NNZ] row of each nonzero (pad: last real row)
    cols: jnp.ndarray      # int32[U-index] of each nonzero (pad: 0)
    vals: jnp.ndarray      # f32[NNZ] (pad: 0)
    labels: jnp.ndarray    # f32[B]
    rweight: jnp.ndarray   # f32[B] per-row example weights (pad: 0)
    row_mask: jnp.ndarray  # f32[B] 1 for real rows
    num_rows: jnp.ndarray  # i32[] actual batch size
    num_uniq: jnp.ndarray  # i32[] actual distinct-feature count

    @property
    def batch_cap(self) -> int:
        return self.labels.shape[0]

    @property
    def nnz_cap(self) -> int:
        return self.vals.shape[0]


class PanelBatch(NamedTuple):
    """Fixed-width row panel: the TPU-preferred batch layout.

    Criteo rows have exactly 39 features (13 int + 26 categorical,
    src/reader/criteo_parser.h:25-115); a [B, F] index matrix turns the
    forward into one gather + dense reductions and the backward into pure
    broadcasts + ONE segment reduction — no per-token COO gathers at all.
    Ragged data still packs here when rows are near-uniform (pad cells:
    idx 0 with val 0); heavily skewed rows fall back to DeviceBatch COO.
    """
    idx: jnp.ndarray       # int32[B, F] positions into the slot vector
    vals: Optional[jnp.ndarray]  # f32[B, F] or None (binary, no padding)
    labels: jnp.ndarray    # f32[B]
    rweight: jnp.ndarray   # f32[B]
    row_mask: jnp.ndarray  # f32[B] 1 for real rows
    num_rows: jnp.ndarray  # i32[]
    num_uniq: jnp.ndarray  # i32[]
    # chunked-run layout (panel_chunk_tokens): the fastest backward. The
    # per-token sorted scatter (a serial ~10 ns/row update loop, half the
    # fused step at bench shapes) becomes a dense vectorised gather+reduce
    # to per-chunk partials plus a scatter of one partial row a chunk.
    # Two tiers when the head arrays are present: the FIRST token of every
    # lane's run is read straight into the lane's own row (``head_row``,
    # no chunk, no partial), and only tokens 2.. of a run are padded into
    # fixed-L gather chunks, so a batch needs sum(ceil((len - 1) / L))
    # chunks and a lane touched once costs one gathered row. Without the
    # head arrays every token is chunked (sum(ceil(len / L)) chunks: one
    # padded chunk at least a lane). Staged once per batch.
    chunk_idx: Optional[jnp.ndarray] = None   # i32[C, L] token row ids
    chunk_lane: Optional[jnp.ndarray] = None  # i32[C] ascending lanes
    chunk_vals: Optional[jnp.ndarray] = None  # f32[C, L] (None if binary)
    head_row: Optional[jnp.ndarray] = None    # i32[U] first token's row id
    head_vals: Optional[jnp.ndarray] = None   # f32[U] (None if binary)

    def with_chunks(self, layout) -> "PanelBatch":
        """This batch carrying a chunk layout as the builders return it:
        ``(chunk_idx, chunk_lane, chunk_vals)``, or those and
        ``(head_row, head_vals)``."""
        return self._replace(**dict(zip(CHUNK_FIELDS, layout)))

    @property
    def batch_cap(self) -> int:
        return self.labels.shape[0]

    @property
    def width(self) -> int:
        return self.idx.shape[1]


CHUNK_FIELDS = ("chunk_idx", "chunk_lane", "chunk_vals", "head_row",
                "head_vals")


def panel_width(blk: RowBlock, batch_cap: int) -> Optional[int]:
    """Fixed panel width for this block, or None when the COO layout is
    denser. Panel wins when B*F_max stays within ~1.5x the COO nnz pad."""
    counts = np.diff(blk.offset)
    if len(counts) == 0:
        return None
    fmax = int(counts.max())
    if fmax == 0:
        return None
    coo_cells = bucket(blk.nnz)
    if batch_cap * fmax <= 1.5 * coo_cells:
        return fmax
    return None


def _panel_arrays(blk: RowBlock, batch_cap: int, width: int):
    """Host-side panel arrays: (idx[B,F], vals[B,F] or None, labels,
    rweight, row_mask)."""
    b = blk.size
    counts = np.diff(blk.offset).astype(np.int64)
    if counts.size and counts.max() > width:
        raise ValueError(f"row nnz {counts.max()} exceeds panel width "
                         f"{width}")
    uniform = counts.size and (counts == width).all()
    if uniform and b == batch_cap:
        idx = blk.index.reshape(b, width).astype(np.int32)
        vals = (None if blk.value is None
                else blk.value.reshape(b, width).astype(REAL_DTYPE))
    else:
        idx = np.zeros((batch_cap, width), dtype=np.int32)
        vals = np.zeros((batch_cap, width), dtype=REAL_DTYPE)
        starts = np.asarray(blk.offset[:-1], dtype=np.int64)
        cell = (np.arange(blk.nnz, dtype=np.int64)
                - np.repeat(starts - blk.offset[0], counts))
        rows_coo = np.repeat(np.arange(b, dtype=np.int64), counts)
        idx[rows_coo, cell] = blk.index.astype(np.int32)
        vals[rows_coo, cell] = blk.values_or_ones()

    labels = np.zeros(batch_cap, dtype=REAL_DTYPE)
    labels[:b] = blk.label
    rweight = np.zeros(batch_cap, dtype=REAL_DTYPE)
    rweight[:b] = blk.weight if blk.weight is not None else 1.0
    row_mask = np.zeros(batch_cap, dtype=REAL_DTYPE)
    row_mask[:b] = 1.0
    return idx, vals, labels, rweight, row_mask


def pad_panel(blk: RowBlock, num_uniq: int, batch_cap: int, width: int
              ) -> PanelBatch:
    """Pack a *localized* row block into a PanelBatch."""
    idx, vals, labels, rweight, row_mask = _panel_arrays(blk, batch_cap,
                                                         width)
    return PanelBatch(
        idx=jnp.asarray(idx),
        vals=None if vals is None else jnp.asarray(vals),
        labels=jnp.asarray(labels), rweight=jnp.asarray(rweight),
        row_mask=jnp.asarray(row_mask),
        num_rows=jnp.asarray(blk.size, dtype=jnp.int32),
        num_uniq=jnp.asarray(num_uniq, dtype=jnp.int32),
    )


def pack_panel(blk: RowBlock, num_uniq: int, slots: np.ndarray,
               batch_cap: int, width: int, u_cap: int,
               counts: Optional[np.ndarray] = None):
    """Panel equivalent of pack_batch: TWO host buffers per batch.

    i32 = [idx(B*F) | slots(u_cap, pre-padded via pad_slots_oob) | b, nu];
    f32 = [vals(B*F)? | labels(B) | rweight(B) | row_mask(B) | counts(u)?].
    ``idx`` addresses slot rows directly (collision dedup happens on the
    host before packing).
    """
    if len(slots) != u_cap:
        raise ValueError(f"slots must arrive pre-padded to u_cap={u_cap}")
    idx, vals, labels, rweight, row_mask = _panel_arrays(blk, batch_cap,
                                                         width)
    binary = vals is None
    cells = batch_cap * width
    i32 = np.empty(cells + u_cap + 2, dtype=np.int32)
    i32[:cells] = idx.reshape(-1)
    i32[cells:cells + u_cap] = slots
    i32[cells + u_cap:] = (blk.size, num_uniq)
    vals_n = 0 if binary else cells
    nf32 = vals_n + 3 * batch_cap + (u_cap if counts is not None else 0)
    f32 = np.zeros(max(nf32, 1), dtype=REAL_DTYPE)
    o = 0
    if not binary:
        f32[:cells] = vals.reshape(-1)
        o = cells
    f32[o:o + batch_cap] = labels
    o += batch_cap
    f32[o:o + batch_cap] = rweight
    o += batch_cap
    f32[o:o + batch_cap] = row_mask
    o += batch_cap
    if counts is not None:
        f32[o:o + len(counts)] = counts
    return i32, f32, binary


@names.leg(names.UNPACK)
def unpack_panel(i32, f32, batch_cap: int, width: int, u_cap: int,
                 has_counts: bool = False, binary: bool = False):
    """jit-traceable inverse of pack_panel ->
    (PanelBatch, slots, counts-or-None)."""
    cells = batch_cap * width
    idx = i32[:cells].reshape(batch_cap, width)
    slots = i32[cells:cells + u_cap]
    meta = i32[cells + u_cap:]
    o = 0
    vals = None
    if not binary:
        vals = f32[:cells].reshape(batch_cap, width)
        o = cells
    labels = f32[o:o + batch_cap]
    o += batch_cap
    rweight = f32[o:o + batch_cap]
    o += batch_cap
    row_mask = f32[o:o + batch_cap]
    o += batch_cap
    counts = f32[o:o + u_cap] if has_counts else None
    pb = PanelBatch(idx=idx, vals=vals, labels=labels, rweight=rweight,
                    row_mask=row_mask, num_rows=meta[0], num_uniq=meta[1])
    return pb, slots, counts


def pack_panel_raw(blk: RowBlock, num_uniq: int, batch_cap: int,
                   width: int):
    """Device-dedup panel payload (ISSUE 13): the block's index cells are
    RAW hashed slot tokens (hash_slots output, NOT localized lanes) and
    there is no slots section — the jit step derives the sorted-unique
    slot vector and the inverse map on device (ops/fused.dedup_tokens),
    so the producer skips the O(nnz log nnz) host ``np.unique``.

    i32 = [tok(B*F) | b, num_uniq]; f32 = [vals(B*F)? | labels(B) |
    rweight(B) | row_mask(B)]. ``num_uniq`` is the host's cheap distinct
    count (pack_stream._count_distinct) — it sizes the sticky u-cap, the
    device recomputes the exact lane count. Pad cells carry token 0
    (TRASH_SLOT), whose gathered row is the all-zero trash row and whose
    gradient contribution is zero (vals 0), so the extra lane it may add
    is trajectory-inert. No counts section: the raw path only engages on
    epochs past the count push (pack_stream.prepare_hashed)."""
    idx, vals, labels, rweight, row_mask = _panel_arrays(blk, batch_cap,
                                                         width)
    binary = vals is None
    cells = batch_cap * width
    i32 = np.empty(cells + 2, dtype=np.int32)
    i32[:cells] = idx.reshape(-1)
    i32[cells:] = (blk.size, num_uniq)
    vals_n = 0 if binary else cells
    f32 = np.zeros(max(vals_n + 3 * batch_cap, 1), dtype=REAL_DTYPE)
    o = 0
    if not binary:
        f32[:cells] = vals.reshape(-1)
        o = cells
    f32[o:o + batch_cap] = labels
    o += batch_cap
    f32[o:o + batch_cap] = rweight
    o += batch_cap
    f32[o:o + batch_cap] = row_mask
    return i32, f32, binary


@names.leg(names.UNPACK)
def unpack_panel_raw(i32, f32, batch_cap: int, width: int,
                     binary: bool = False):
    """jit-traceable inverse of pack_panel_raw -> (PanelBatch with RAW
    token idx cells, num_uniq meta). The caller runs dedup_tokens over
    the flat cells and rewrites ``idx`` to the localized inverse."""
    cells = batch_cap * width
    idx = i32[:cells].reshape(batch_cap, width)
    meta = i32[cells:]
    o = 0
    vals = None
    if not binary:
        vals = f32[:cells].reshape(batch_cap, width)
        o = cells
    labels = f32[o:o + batch_cap]
    o += batch_cap
    rweight = f32[o:o + batch_cap]
    o += batch_cap
    row_mask = f32[o:o + batch_cap]
    return PanelBatch(idx=idx, vals=vals, labels=labels, rweight=rweight,
                      row_mask=row_mask, num_rows=meta[0],
                      num_uniq=meta[1])


# Chunk length of the run-chunked backward layout. L=16 measured fastest at
# bench shapes when every token was chunked (L=8: more chunks to scatter;
# L=32/64: more gather padding on the zipf run-length distribution). With
# the head tier a lane touched once needs no chunk at all, whatever L.
CHUNK_L = 16


def chunk_cap(u_cap: int, cells: int, L: int = CHUNK_L) -> int:
    """Static chunk-count bound, from shapes alone: every one of the
    <= u_cap lane runs wastes less than one chunk of padding, plus cells/L
    full chunks. It holds for ANY batch of the shape, with or without the
    head tier; what a batch needs is counted by :func:`chunks_needed`, and
    the sticky ``<job>.c`` cap (data/pack_stream.ShapeSchedule.chunk_cap)
    follows that count and never passes this bound."""
    return u_cap + cells // L + 2


def chunks_needed(flat_idx: np.ndarray, u_cap: int, L: int = CHUNK_L,
                  head: bool = True) -> int:
    """The chunks a batch's lanes need (host side, one bincount over the
    panel's cells): sum over lanes of ceil((len - 1) / L) with the head
    tier, of ceil(len / L) without. A batch of lanes touched once each
    needs none."""
    return int(_lane_chunks(np.bincount(flat_idx, minlength=u_cap), L,
                            head).sum())


def _lane_chunks(cnt, L: int, head: bool):
    """Chunks per lane from tokens per lane (0 for an untouched lane,
    and with the head tier for a lane touched once)."""
    return (cnt + (L - 1 - bool(head))) // L


def _chunk_layout(xp, order, cnt, vals_sorted, u_cap: int, b_fill: int,
                  width: int, L: int, C: int, row_base: int, head: bool):
    """The layout from the lane-sorted token order, gathers and cumsums
    alone; ``xp`` is numpy or jax.numpy (one definition, so the host and
    the device builder cannot drift apart). ``order[i]`` is the panel
    cell of the i-th token in lane order, ``cnt[u]`` lane u's tokens."""
    cells = order.shape[0]
    skip = 1 if head else 0
    run_end = xp.cumsum(cnt)
    run_start = run_end - cnt                   # lane u's first token
    n_chunks = _lane_chunks(cnt, L, head)
    chunk_end = xp.cumsum(n_chunks)
    cidx = xp.arange(C)
    # the lane that owns chunk c; u_cap (out of bounds) past the last
    cl = xp.searchsorted(chunk_end, cidx, side="right")
    used = cl < u_cap
    lc = xp.minimum(cl, u_cap - 1)
    pos = (run_start[lc] + skip
           + (cidx - (chunk_end - n_chunks)[lc]) * L)[:, None] \
        + xp.arange(L)[None, :]                 # [C, L] sorted positions
    valid = (pos < run_end[lc][:, None]) & used[:, None]
    pos = xp.minimum(pos, cells - 1)
    ci = xp.where(valid, order[pos] // width + row_base, b_fill)
    cv = None
    if vals_sorted is not None:
        cv = xp.where(valid, vals_sorted[pos], 0).astype(vals_sorted.dtype)
    out = (ci.astype(xp.int32), xp.where(used, cl, u_cap).astype(xp.int32),
           cv)
    if not head:
        return out
    first = xp.minimum(run_start, cells - 1)
    hr = xp.where(cnt > 0, order[first] // width + row_base, b_fill)
    hv = None
    if vals_sorted is not None:
        hv = xp.where(cnt > 0, vals_sorted[first],
                      0).astype(vals_sorted.dtype)
    return out + (hr.astype(xp.int32), hv)


def panel_chunk_tokens_flat(flat_idx: jnp.ndarray,
                            flat_vals: Optional[jnp.ndarray],
                            u_cap: int, b_cap: int, width: int,
                            L: int = CHUNK_L, C: Optional[int] = None,
                            head: bool = False):
    """Chunked-run backward layout from flat panel lanes (jit-traceable;
    run ONCE per batch at device-cache staging time).

    Tokens are lane-sorted. With ``head`` the first token of each lane's
    run goes to the head tier and tokens 2.. are split into
    ceil((len - 1)/L) chunks of exactly L gather slots; without it the
    whole run is split into ceil(len/L) chunks (pad -> ``b_cap``, past
    the batch's rows: the backward reads zeros there). Returns

      chunk_idx  i32[C, L]  token row ids per chunk,
      chunk_lane i32[C]     ascending output lane per chunk (pad -> u_cap,
                            dropped by the reduction's mode="drop"),
      chunk_vals f32[C, L]  per-token values (None when ``flat_vals`` is),

    and with ``head`` also

      head_row   i32[u_cap] the row id of lane u's first token (lanes the
                            batch does not touch -> ``b_cap``),
      head_vals  f32[u_cap] its value (None when ``flat_vals`` is).

    ``C`` defaults to chunk_cap(u_cap, cells, L), a function of static
    shapes only; a caller that has counted the batch's chunks on the host
    (:func:`chunks_needed`) passes its sticky cap, and chunks past ``C``
    would be lost. Used chunks form a prefix and their lanes are
    ascending; runs split across chunks simply scatter-add multiple
    partials into the same lane."""
    cells = flat_idx.shape[0]
    if C is None:
        C = chunk_cap(u_cap, cells, L)
    order = jnp.argsort(flat_idx).astype(jnp.int32)
    cnt = jnp.bincount(flat_idx, length=u_cap)
    vs = None if flat_vals is None else flat_vals[order]
    return _chunk_layout(jnp, order, cnt, vs, u_cap, b_cap, width, L, C,
                         0, head)


def panel_chunk_tokens_np(flat_idx: np.ndarray,
                          flat_vals: Optional[np.ndarray],
                          u_cap: int, b_fill: int, width: int,
                          L: int = CHUNK_L, C: Optional[int] = None,
                          row_base: int = 0, head: bool = False):
    """Host-side (numpy) twin of :func:`panel_chunk_tokens_flat`, for the
    mesh/SPMD paths and the streamed producers, where the chunk layout is
    built at batch-prep time rather than on device at cache-staging time:

    - ``row_base`` offsets token row ids into the GLOBAL dp-concatenated
      row space (this host's rows live at [row_base, row_base + b_local));
    - ``b_fill`` is the out-of-bounds pad row (the GLOBAL batch cap for
      sharded batches), so pad cells read zeros in the backward;
    - ``C`` pins the chunk count explicitly — the sticky cap of a
      schedule, rounded up by mesh callers to a multiple of the dp axis
      so the [C, L] arrays shard evenly; a batch that needs more raises.

    Tokens are lane-sorted per host, so each host's chunk_lane block is
    ascending — but the dp-concatenation of blocks is NOT globally
    sorted, which is why the mesh step drops the ``indices_are_sorted``
    promise (losses/fm.py ``chunks_sorted``)."""
    cells = len(flat_idx)
    if C is None:
        C = chunk_cap(u_cap, cells, L)
    cnt = np.bincount(flat_idx, minlength=u_cap)[:u_cap]
    need = int(_lane_chunks(cnt, L, head).sum())
    if need > C:
        raise ValueError(f"chunk count {need} exceeds cap {C}")
    order = np.argsort(flat_idx, kind="stable")
    vs = None if flat_vals is None else flat_vals[order]
    return _chunk_layout(np, order, cnt, vs, u_cap, b_fill, width, L, C,
                         row_base, head)


def panel_chunk_tokens(pb: PanelBatch, u_cap: int, L: int = CHUNK_L,
                       C: Optional[int] = None,
                       head: bool = False) -> PanelBatch:
    """Attach the chunked-run backward layout to a panel batch. ``u_cap``
    is the batch's lane-space size (its slot vector length)."""
    B, F = pb.idx.shape
    flat = pb.idx.reshape(B * F)
    fv = None if pb.vals is None else pb.vals.reshape(B * F)
    return pb.with_chunks(
        panel_chunk_tokens_flat(flat, fv, u_cap, B, F, L, C, head))


def bucket(n: int, minimum: int = 8) -> int:
    """Round up to the next bucket rung (>= minimum).

    Rungs are {m*2^j, 1.5*m*2^j} for m = ``minimum``: at most 33% padding
    waste instead of 2x. Every rung is divisible by d whenever ``minimum``
    is a multiple of 2*d (1.5*m*2^j = 3*(m/2)*2^j) — callers sharding the
    dimension over a mesh axis must pass ``mesh_dim_min(d)``."""
    b = minimum
    while b < n:
        if n <= b + b // 2:
            return b + b // 2
        b *= 2
    return b


def row_cap(n: int, minimum: int = 8) -> int:
    """Round the step's unique-row dimension (the ``<job>.u`` cap) up to
    its rung: bucket()'s ladder with eighths between the powers of two.

    Rungs are (8+i)/8 * m*2^j, i = 0..7, m = ``minimum``: at most 12.5% of
    the cap is padding where bucket() leaves up to 33%. Every leg of the
    step is sized by this dimension (or by chunk_cap of it), so its
    padding is paid in device time by every step. A fine rung (i not 0
    or 4) is used only where it is a multiple of 1024 rows and above
    8192: the dimension is minor in the narrow row's transposed operand
    and major elsewhere, and 8 sublanes x 128 lanes tile it both ways
    without a pad. Up to 8192 the result is bucket()'s, and every rung of
    bucket() stays a rung, so a cap taken from an older run is valid."""
    b = minimum
    while 2 * b < n:
        b *= 2
    if n <= b:
        return b
    for i in range(1, 8):
        eighths = b * (8 + i)
        if i == 4:
            rung = b + b // 2
        elif eighths % 8192 == 0 and eighths > 8 * 8192:
            rung = eighths // 8
        else:
            continue
        if n <= rung:
            return rung
    return 2 * b


def mesh_dim_min(dp: int, floor: int = 8) -> int:
    """Bucket minimum that keeps every rung divisible by ``dp``: the
    smallest multiple of 2*dp that is >= floor. Needed because bucket()'s
    1.5x rungs are only divisible by dp when the floor carries a factor of
    2*dp (e.g. dp=3, floor 8 would yield rungs 8, 12, 16 — 8 and 16 split
    unevenly over a 3-way axis)."""
    base = 2 * dp
    return base * ((max(floor, base) + base - 1) // base)


def pack_batch(blk: RowBlock, num_uniq: int, slots: np.ndarray,
               batch_cap: int, nnz_cap: int, u_cap: int,
               counts: Optional[np.ndarray] = None):
    """Pack a localized block + slot vector into TWO host buffers
    (int32 + float32) so staging costs two device transfers instead of
    eight.

    Layout (static per bucket): i32 = [rows(nnz) | cols(nnz) | slots(u)];
    f32 = [vals(nnz)? | labels(B) | rweight(B) | row_mask(B) |
    counts(u)?]. Binary blocks (value is None — e.g. criteo) omit the vals
    section and reconstruct ones*row-validity on device, halving the f32
    payload. ``cols`` address slot rows directly (host-side dedup).
    ``unpack_batch`` is the jit-side inverse.
    """
    b, nnz = blk.size, blk.nnz
    if b > batch_cap or nnz > nnz_cap:
        raise ValueError("batch exceeds caps")
    if len(slots) != u_cap:
        # the device kernels declare sorted+unique indices; a short vector
        # zero-padded here would put TRASH_SLOT=0 after larger slots and
        # break both declarations — callers must pre-pad with
        # store.local.pad_slots_oob (ascending out-of-bounds padding)
        raise ValueError(
            f"slots must arrive pre-padded to u_cap={u_cap} "
            f"(got {len(slots)}); use pad_slots_oob")
    binary = blk.value is None
    # trailing 3 ints: [b, num_uniq, nnz] — kept in the i32 buffer so they
    # stay exact (f32 would round past 2^24)
    i32 = np.zeros(2 * nnz_cap + u_cap + 3, dtype=np.int32)
    i32[:nnz] = blk.row_ids()
    i32[nnz:nnz_cap] = max(b - 1, 0)  # pad rows -> a real segment, vals 0
    i32[nnz_cap:nnz_cap + nnz] = blk.index.astype(np.int32)
    i32[2 * nnz_cap:2 * nnz_cap + u_cap] = slots
    i32[2 * nnz_cap + u_cap:] = (b, num_uniq, nnz)

    vals_n = 0 if binary else nnz_cap
    nf32 = vals_n + 3 * batch_cap \
        + (u_cap if counts is not None else 0)
    f32 = np.zeros(max(nf32, 1), dtype=REAL_DTYPE)
    o = 0
    if not binary:
        f32[:nnz] = blk.value
        o = nnz_cap
    f32[o:o + b] = blk.label
    o += batch_cap
    f32[o:o + b] = blk.weight if blk.weight is not None else 1.0
    o += batch_cap
    f32[o:o + b] = 1.0
    o += batch_cap
    if counts is not None:
        f32[o:o + len(counts)] = counts
    return i32, f32, binary


@names.leg(names.UNPACK)
def unpack_batch(i32, f32, batch_cap: int, nnz_cap: int, u_cap: int,
                 has_counts: bool = False, binary: bool = False):
    """jit-traceable inverse of pack_batch ->
    (DeviceBatch, slots, counts-or-None)."""
    import jax.numpy as jnp

    rows = i32[:nnz_cap]
    cols = i32[nnz_cap:2 * nnz_cap]
    slots = i32[2 * nnz_cap:2 * nnz_cap + u_cap]
    meta = i32[2 * nnz_cap + u_cap:]  # [b, num_uniq, nnz], exact int32
    if binary:
        # all-ones values, zeroed on padding entries (value elision,
        # src/reader/batch_reader.cc:71-73 carried to the device side)
        iota = jnp.arange(nnz_cap, dtype=jnp.int32)
        vals = (iota < meta[2]).astype(jnp.float32)
        o = 0
    else:
        vals = f32[:nnz_cap]
        o = nnz_cap
    labels = f32[o:o + batch_cap]
    o += batch_cap
    rweight = f32[o:o + batch_cap]
    o += batch_cap
    row_mask = f32[o:o + batch_cap]
    o += batch_cap
    counts = None
    if has_counts:
        counts = f32[o:o + u_cap]
    batch = DeviceBatch(
        rows=rows, cols=cols, vals=vals, labels=labels, rweight=rweight,
        row_mask=row_mask,
        num_rows=meta[0],
        num_uniq=meta[1],
    )
    return batch, slots, counts


def pad_batch(blk: RowBlock, num_uniq: int,
              batch_cap: Optional[int] = None,
              nnz_cap: Optional[int] = None) -> DeviceBatch:
    """Pack a *localized* row block (uint32 indices) into a DeviceBatch."""
    b, nnz = blk.size, blk.nnz
    bc = batch_cap or bucket(b)
    nc = nnz_cap or bucket(nnz)
    if b > bc or nnz > nc:
        raise ValueError(f"batch ({b},{nnz}) exceeds caps ({bc},{nc})")

    rows = np.zeros(nc, dtype=np.int32)
    rows[:nnz] = blk.row_ids()
    rows[nnz:] = max(b - 1, 0)  # pad rows point at a real segment; vals=0
    cols = np.zeros(nc, dtype=np.int32)
    cols[:nnz] = blk.index.astype(np.int32)
    vals = np.zeros(nc, dtype=REAL_DTYPE)
    vals[:nnz] = blk.values_or_ones()

    labels = np.zeros(bc, dtype=REAL_DTYPE)
    labels[:b] = blk.label
    rweight = np.zeros(bc, dtype=REAL_DTYPE)
    rweight[:b] = blk.weight if blk.weight is not None else 1.0
    row_mask = np.zeros(bc, dtype=REAL_DTYPE)
    row_mask[:b] = 1.0

    return DeviceBatch(
        rows=jnp.asarray(rows), cols=jnp.asarray(cols), vals=jnp.asarray(vals),
        labels=jnp.asarray(labels), rweight=jnp.asarray(rweight),
        row_mask=jnp.asarray(row_mask),
        num_rows=jnp.asarray(b, dtype=jnp.int32),
        num_uniq=jnp.asarray(num_uniq, dtype=jnp.int32),
    )
