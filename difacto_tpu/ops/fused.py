"""The table-facing halves of the fused sparse-FM step + on-device key dedup.

The SGD hot path is gather -> FM interaction -> scatter-update over the
fused slot-table rows (updaters/sgd_updater.py). This module owns the
two table kernels of that program: :func:`gather_rows` reads the batch's
sorted unique rows ONCE (step.py threads them from pull to push, so the
push never re-gathers), the FTRL/AdaGrad epilogue
(updaters.sgd_updater.row_epilogue) runs on the threaded rows, and
:func:`scatter_rows` writes them back. Both are plain XLA
gather/scatter with the unique flag, and the sorted one where it pays;
under a mesh GSPMD partitions them, or, given a counted ``own_cap``,
each fs shard runs them over the run of the slots it owns.

On-device dedup (:func:`dedup_tokens`): the streamed producer's
``np.unique`` over the batch's O(nnz) hashed tokens is the dominant
remaining host pack cost (data/pack_stream.py). With
``device_dedup=1`` the producer ships RAW token lanes and this sort +
run-length pass builds the sorted-unique slot vector (OOB-padded, the
ops/batch.py contract) and the inverse index map inside the jit step.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..obs import names
from ..parallel.mesh import FS_AXIS, fs_size


# --------------------------------------------------------------- dedup
@names.leg(names.UNPACK)
def dedup_tokens(tok: jnp.ndarray, u_cap: int, capacity: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """On-device twin of the producer's ``np.unique`` + ``pad_slots_oob``
    (data/pack_stream.prepare_hashed, store/local.py): sort the batch's
    raw int32 token lanes, mark run starts, and run-length segment ids
    become the inverse map.

    Returns ``(slots, inverse, n_uniq)``:

    - ``slots`` int32[u_cap] — the sorted unique token values followed
      by ASCENDING out-of-bounds padding (``capacity + j``), exactly
      the pad_slots_oob layout, so the table kernels' sorted+unique
      index declarations stay truthful;
    - ``inverse`` int32[len(tok)] — each lane's position in ``slots``
      (the localized column index the host dedup used to compute);
    - ``n_uniq`` i32[] — the number of real (non-pad) slots.

    The caller guarantees ``n_uniq <= u_cap`` (the producer counts
    distinct tokens with an O(nnz + capacity) flag pass and sizes the
    sticky u-cap with a +1 margin for the TRASH lane pad cells
    introduce — pack_stream.prepare_hashed).
    """
    cells = tok.shape[0]
    order = jnp.argsort(tok)
    st = tok[order]
    start = jnp.concatenate(
        [jnp.ones((1,), bool), st[1:] != st[:-1]])
    seg = jnp.cumsum(start.astype(jnp.int32)) - 1
    n = seg[-1] + 1
    inverse = jnp.zeros(cells, jnp.int32).at[order].set(seg)
    # scatter each run's FIRST token to its segment position (unique
    # writes; non-starts aim at the dropped OOB lane u_cap)
    first = jnp.where(start, seg, u_cap)
    slots = jnp.zeros(u_cap, jnp.int32).at[first].set(st, mode="drop")
    j = jnp.arange(u_cap, dtype=jnp.int32)
    # pad value = capacity + POSITION, byte-identical to the host's
    # pad_slots_oob (arange overwritten by the real prefix)
    slots = jnp.where(j < n, slots, capacity + j)
    return slots, inverse, n


# ------------------------------------------------------- quantized slots
# Per-row symmetric quantization of the fused-row embedding halves
# (capacity lever (a), difacto_tpu/capacity/): codes live in an int8
# container (fp8 bit patterns are bitcast into it — one table dtype for
# both kinds), the per-row f32 scale rides the spare scalar lanes of the
# SAME fused row (updaters/sgd_updater.pack_scal lanes 5/6), so the hot
# path stays exactly one gather + one scatter: dequant/requant are
# elementwise epilogue ops on the already-gathered rows (row_epilogue).
_Q_MAX = {"int8": 127.0, "fp8": 448.0}  # fp8 = float8_e4m3fn finite max


def quant_half(x: jnp.ndarray, kind: str):
    """f32 [n, m] half -> (int8 codes [n, m], f32 scale [n]).

    Symmetric per-row scaling: ``scale = max|row| / qmax`` (1.0 for
    all-zero rows so the dequant is well-defined), int8 codes round to
    [-127, 127], fp8 codes cast to float8_e4m3fn and bitcast into the
    int8 container. Zero-padded lane columns encode as 0 either way."""
    amax = jnp.max(jnp.abs(x), axis=1)
    scale = jnp.where(amax > 0, amax / _Q_MAX[kind], 1.0)
    y = x / scale[:, None]
    if kind == "int8":
        codes = jnp.clip(jnp.round(y), -127.0, 127.0).astype(jnp.int8)
    else:
        codes = jax.lax.bitcast_convert_type(
            y.astype(jnp.float8_e4m3fn), jnp.int8)
    return codes, scale


def dequant_half(codes: jnp.ndarray, scale: jnp.ndarray, kind: str
                 ) -> jnp.ndarray:
    """Inverse of :func:`quant_half`: int8 container codes + per-row
    scale -> f32 values."""
    if kind == "int8":
        f = codes.astype(jnp.float32)
    else:
        f = jax.lax.bitcast_convert_type(
            codes, jnp.float8_e4m3fn).astype(jnp.float32)
    return f * scale[:, None]


# -------------------------------------------------------- table kernels
# What one XLA row scatter costs on a v5e (ISSUE 33's probe: one jitted
# table-donating scatter of sorted unique rows, ms a call, one chip):
#
#   table              indices   indices_are_sorted=True   =False
#   bf16[2^23,256]     294,912   17.5                      23.4
#   bf16[2^23,256]      73,728   16.4                       5.9
#   bf16[2^23,256]       8,192   16.1                       0.70
#   bf16[2^23,256]          64   14.8                       0.21
#   bf16[2^21|22|24,256] 294,912  5.5 / 9.5 / 33.5          7.8 / 23.4 / 23.4
#   f32[2^23,128]      294,912   13.7                      20.6
#   f32[2^23,128]       73,728   13.1                       5.2
#   f32[2^22|24,128]   294,912   7.3 / 26.6                20.6 / 20.6
#   int8[2^23|22,256]  294,912   12.0 / 6.8                23.0 / 23.0
#   f32[2^23] (flat)   294,912   1.5                        1.8
#
# Declared sorted, the scatter SWEEPS the table: ~2.1 ns a table row
# (bf16x256; 1.6 f32x128, 1.4 int8x256) however many indices are in
# range. Undeclared it pays by the INDEX, 63-80 ns each, in range or
# not, whatever the table. They break even near 36 (bf16x256), 41
# (f32x128) and 49 (int8x256) table rows an index; the one-chip cells
# sit at 28.4 and a shard's owned run at 113.8, so one constant between
# them serves every row format. Flat 1-D arrays cost 1.5 ms either way
# and keep the declaration.
SWEEP_ROWS_PER_INDEX = 40


def scatter_sweeps(table_rows: int, n_indices: int) -> bool:
    """Whether a row scatter of ``n_indices`` sorted rows into a table of
    ``table_rows`` (one device's share of it) should declare
    ``indices_are_sorted``: the declaration buys a sweep over the table,
    which wins only while the table is small beside the batch (the
    probe above)."""
    return bool(table_rows <= SWEEP_ROWS_PER_INDEX * n_indices)


# whole rows of a 2-D table by one index a row, as ``.at[slots]`` spells
_ROWS = jax.lax.GatherDimensionNumbers(
    offset_dims=(1,), collapsed_slice_dims=(0,), start_index_map=(0,))
_ROWS_BACK = jax.lax.ScatterDimensionNumbers(
    update_window_dims=(1,), inserted_window_dims=(0,),
    scatter_dims_to_operand_dims=(0,))


def _owned(table, slots, mesh, own_cap) -> bool:
    """Whether the table legs run over each shard's owned run: a fused-
    row table under ``mesh_fs > 1`` and a counted ``own_cap`` below the
    row cap. Everything else runs the plain call, which GSPMD
    partitions under a mesh."""
    return (own_cap is not None and table.ndim == 2
            and fs_size(mesh) > 1 and own_cap < slots.shape[0])


def _owned_run(slots, own_cap: int, shard_rows: int):
    """Inside a shard_map over ``fs``: ``(start, local)`` of the calling
    shard's run of the sorted unique ``slots``. The table is sharded by
    key range (parallel/mesh.fs_shard_bounds), so the rows shard k owns
    are one contiguous run; ``start`` is where it begins, clamped so
    ``own_cap`` entries fit (the clamp dynamic_slice applies anyway),
    and ``local`` the ``own_cap`` slots from there as rows of the
    shard's own table. Entries the shard does not own — the next
    shard's rows and the ascending pads above its range, the previous
    shard's below zero after a clamp — stay out of range and keep the
    run ascending and unique; the callers' FILL_OR_DROP drops them
    (``.at[]`` would wrap the negative ones)."""
    lo = jax.lax.axis_index(FS_AXIS) * shard_rows
    start = jnp.minimum(jnp.sum(slots < lo, dtype=jnp.int32),
                        slots.shape[0] - own_cap)
    return start, jax.lax.dynamic_slice(slots, (start,), (own_cap,)) - lo


@names.leg(names.GATHER)
def gather_rows(table: jnp.ndarray, slots: jnp.ndarray, mesh=None,
                own_cap: Optional[int] = None) -> jnp.ndarray:
    """ONE fused-row gather of the batch's sorted unique slots.

    The store guarantees sorted unique slots with ascending
    out-of-bounds padding (pad_slots_oob), the flags let XLA skip
    duplicate handling (~20% off the fused step,
    updaters/sgd_updater.py), and padded lanes read zeros
    (mode=fill).

    With ``mesh`` and a counted ``own_cap`` (learners/sgd.py: no shard
    owns more of ``slots``) each fs shard gathers its owned run alone,
    ``own_cap`` rows and not the row cap, into its place in a zero
    operand; the sum over ``fs`` is the exchange GSPMD's partitioning
    of the plain call makes too. It runs on the rows' bits (every
    position has one owner, the rest add zeros), so the f32 halves that
    ride bf16 lanes pass unrounded."""
    if not _owned(table, slots, mesh, own_cap):
        return table.at[slots].get(indices_are_sorted=True,
                                   unique_indices=True,
                                   mode="fill", fill_value=0)
    bits = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[
        table.dtype.itemsize]

    def shard(tab, slots):
        start, local = _owned_run(slots, own_cap, tab.shape[0])
        got = jax.lax.gather(
            tab, local[:, None], _ROWS, (1, tab.shape[1]),
            indices_are_sorted=True, unique_indices=True,
            mode=jax.lax.GatherScatterMode.FILL_OR_DROP, fill_value=0)
        return jax.lax.dynamic_update_slice(
            jnp.zeros((1, slots.shape[0], tab.shape[1]), bits),
            jax.lax.bitcast_convert_type(got, bits)[None], (0, start, 0))

    # one operand a shard, summed outside the manual region: the sum
    # over the sharded axis is the all-reduce GSPMD makes of the plain
    # call too, under the name the trace readers know it by
    parts = jax.shard_map(shard, mesh=mesh,
                          in_specs=(P(FS_AXIS, None), P()),
                          out_specs=P(FS_AXIS, None, None))(table, slots)
    return jax.lax.bitcast_convert_type(
        jnp.sum(parts, axis=0, dtype=bits), table.dtype)


@names.leg(names.SCATTER)
def scatter_rows(table: jnp.ndarray, slots: jnp.ndarray,
                 rows: jnp.ndarray, mesh=None,
                 own_cap: Optional[int] = None) -> jnp.ndarray:
    """Write ``rows`` back at ``slots`` (padded OOB entries dropped).

    ``indices_are_sorted`` is declared where the sweep it buys is the
    cheaper form (:func:`scatter_sweeps`, by one device's table rows
    and the index count). With ``mesh`` and ``own_cap`` as in
    :func:`gather_rows`, each shard writes its owned run alone, in
    place on its own rows."""
    if not _owned(table, slots, mesh, own_cap):
        srt = table.ndim == 1 or scatter_sweeps(
            table.shape[0] // fs_size(mesh), slots.shape[0])
        return table.at[slots].set(rows, indices_are_sorted=srt,
                                   unique_indices=True, mode="drop")

    def shard(tab, slots, rows):
        start, local = _owned_run(slots, own_cap, tab.shape[0])
        new = jax.lax.dynamic_slice(rows, (start, 0),
                                    (own_cap, rows.shape[1]))
        return jax.lax.scatter(
            tab, local[:, None], new, _ROWS_BACK,
            indices_are_sorted=scatter_sweeps(tab.shape[0], own_cap),
            unique_indices=True,
            mode=jax.lax.GatherScatterMode.FILL_OR_DROP)

    return jax.shard_map(shard, mesh=mesh,
                         in_specs=(P(FS_AXIS, None), P(), P()),
                         out_specs=P(FS_AXIS, None))(table, slots, rows)
