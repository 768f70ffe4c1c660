"""The table-facing halves of the fused sparse-FM step + on-device key dedup.

The SGD hot path is gather -> FM interaction -> scatter-update over the
fused slot-table rows (updaters/sgd_updater.py). This module owns the
two table kernels of that program: :func:`gather_rows` reads the batch's
sorted unique rows ONCE (step.py threads them from pull to push, so the
push never re-gathers), the FTRL/AdaGrad epilogue
(updaters.sgd_updater.row_epilogue) runs on the threaded rows, and
:func:`scatter_rows` writes them back. Both are plain XLA
gather/scatter with the sorted+unique index flags; GSPMD partitions
them under a mesh.

On-device dedup (:func:`dedup_tokens`): the streamed producer's
``np.unique`` over the batch's O(nnz) hashed tokens is the dominant
remaining host pack cost (data/pack_stream.py). With
``device_dedup=1`` the producer ships RAW token lanes and this sort +
run-length pass builds the sorted-unique slot vector (OOB-padded, the
ops/batch.py contract) and the inverse index map inside the jit step.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..obs import names


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
@names.leg(names.GATHER)
def gather_rows(table: jnp.ndarray, slots: jnp.ndarray) -> jnp.ndarray:
    """ONE fused-row gather of the batch's sorted unique slots.

    The store guarantees sorted unique slots with ascending
    out-of-bounds padding (pad_slots_oob), the flags let XLA skip
    duplicate handling (~20% off the fused step,
    updaters/sgd_updater.py), and padded lanes read zeros
    (mode=fill)."""
    return table.at[slots].get(indices_are_sorted=True,
                               unique_indices=True,
                               mode="fill", fill_value=0)


@names.leg(names.SCATTER)
def scatter_rows(table: jnp.ndarray, slots: jnp.ndarray,
                 rows: jnp.ndarray) -> jnp.ndarray:
    """Write ``rows`` back at ``slots`` (padded OOB entries dropped)."""
    return table.at[slots].set(rows, indices_are_sorted=True,
                               unique_indices=True, mode="drop")
