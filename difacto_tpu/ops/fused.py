"""Fused sparse-FM kernel backends + on-device key dedup (ROADMAP item 3).

The SGD hot path is gather -> FM interaction -> scatter-update over the
fused slot-table rows (updaters/sgd_updater.py). This module owns the
TABLE-FACING halves of that program behind a ``fused_kernel`` knob
(``auto|pallas|jnp|off``, SGDUpdaterParam):

- ``jnp`` — the carefully fused single-program path: the step gathers
  the fused rows ONCE (step.py threads them from pull to push instead
  of relying on XLA CSE to merge the pull/push gathers), the
  FTRL/AdaGrad epilogue runs on the threaded rows, and one scatter
  writes them back. Identical primitives to ``off``, so trajectories
  are byte-identical by construction.
- ``pallas`` — the same dataflow with the gather and the
  epilogue+scatter as ``pl.pallas_call`` kernels: scalar-prefetched
  slot indices drive per-row async DMAs between the HBM-resident table
  and VMEM row tiles, and the scatter kernel folds the per-row
  FTRL/AdaGrad update into its epilogue before the write-back — the
  table row moves through HBM exactly twice per step (out on the pull,
  back on the push) with no composed-op round trips between. The
  update math is the SAME ``row_epilogue`` function the jnp path
  scatters (traced into the kernel per tile). Off-TPU the kernels run
  in Pallas interpret mode — the parity harness (``make
  kernel-parity``). ON a TPU backend the knob is refused, typed
  (:class:`PallasRefused`): Mosaic does not compile these kernels, see
  ``_MOSAIC_REFUSAL``.
- ``off`` — the pre-ISSUE-13 composed path (get_rows + apply_grad as
  separate gather/scatter programs, merged only by XLA CSE).

History note: the round-3 per-row-DMA scaffold was measured
latency-bound and deleted — it moved BARE rows, so it competed with one
XLA gather. This kernel revisits the design with the update folded into
the scatter's epilogue (halving the table traffic the composed path
pays) and R-row tiles whose DMAs issue before any wait. ``auto``
resolves to ``jnp``.

On-device dedup (:func:`dedup_tokens`): the streamed producer's
``np.unique`` over the batch's O(nnz) hashed tokens is the dominant
remaining host pack cost (data/pack_stream.py). With
``device_dedup=1`` the producer ships RAW token lanes and this sort +
run-length pass builds the sorted-unique slot vector (OOB-padded, the
ops/batch.py contract) and the inverse index map inside the jit step.
"""

from __future__ import annotations

import logging
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..obs import names
from ..utils import jaxtrace

log = logging.getLogger("difacto_tpu")

# rows per pallas grid step: every ShapeSchedule/bucket rung >= 8 is
# divisible by 4 (ops/batch.bucket — {8*2^j, 12*2^j} rungs), so a tile
# of 8 or 4 rows always divides u_cap and the kernels need no tail
# masking. 8 row-DMAs in flight per tile amortizes the per-copy latency
# that killed the round-3 single-row scaffold.
_TILE_ROWS = 8

_BACKENDS = ("auto", "pallas", "jnp", "off")

# What Mosaic said when both kernels first went to the compiler (PR 21:
# TPU v5 lite, jax/jaxlib 0.9.0, libtpu 0.0.34; gather_rows,
# scatter_rows and fm_update_rows jitted with backend="pallas" over
# [2^21, 256] bf16, [2^20, 256] f32 and [2^20, 256] int8 tables,
# u_cap = 131072). The per-row DMA — the design itself — is refused at
# every dtype on both sides of the copy (``tbl_ref.at[s]`` in HBM,
# ``scratch.at[j]`` in VMEM), and the in-kernel epilogue is refused on
# the packed dtypes at row_epilogue's shape-changing bitcast of the
# scalar lanes.
_MOSAIC_REFUSAL = (
    "MosaicError: INTERNAL: Mosaic failed to compile TPU kernel: Slice "
    "shape along dimension 0 must be aligned to tiling (8), but is 1. "
    "[tpu.memref_slice of the table / the VMEM row tile to one row; "
    "f32, bf16 and int8] | NotImplementedError: Changing bitwidths not "
    "supported. [row_epilogue traced into the kernel; bf16 and int8]")


class PallasRefused(ValueError):
    """``fused_kernel=pallas`` on a TPU backend: the kernels do not
    compile there, and nothing falls back to interpret mode."""


def interpret_mode() -> bool:
    """Pallas kernels compile through Mosaic only on TPU backends;
    everywhere else they run interpreted — slow, and only meant for the
    parity tests. Never true on a TPU backend."""
    return jax.default_backend() != "tpu"


def resolve_backend(knob: str, mesh=None, V_dim: int = 0) -> str:
    """``fused_kernel`` knob -> concrete backend for this store.

    - ``off`` (or a flat ``V_dim == 0`` table, which has no fused row
      to kernel over) keeps the composed path;
    - ``jnp`` is the fused single-program path, valid everywhere
      (mesh included — same primitives, GSPMD partitions them);
    - ``pallas`` requires an unsharded table (a pallas_call is opaque
      to GSPMD: under fs-sharding it would force the table through a
      replicated intermediate, exactly what state_constrainer exists
      to prevent) and a non-TPU backend (interpret mode; on a TPU
      Mosaic refuses the kernels, :class:`PallasRefused`) — it fails
      typed rather than silently degrading;
    - ``auto`` resolves to ``jnp``; it never picks pallas on its own.
    """
    if knob not in _BACKENDS:
        raise ValueError(
            f"unknown fused_kernel {knob!r} (expected auto|pallas|jnp|off)")
    if knob == "off" or V_dim == 0:
        reason = ("fused_kernel=off" if knob == "off"
                  else "flat table (V_dim=0) has no fused row")
        return _log_resolution(knob, "off", reason)
    if knob == "pallas":
        if mesh is not None:
            raise ValueError(
                "fused_kernel=pallas does not support a sharded table "
                "(mesh_fs/mesh_dp > 1 or mesh_force): pallas_call is "
                "opaque to GSPMD partitioning — use fused_kernel=jnp "
                "for mesh runs")
        if not interpret_mode():
            raise PallasRefused(
                "fused_kernel=pallas does not compile on a TPU backend "
                f"— Mosaic's words: {_MOSAIC_REFUSAL} Use "
                "fused_kernel=jnp (what auto selects).")
        return _log_resolution(knob, "pallas",
                               "interpret mode (parity harness)")
    if knob == "jnp":
        return _log_resolution(knob, "jnp", "explicit knob")
    return _log_resolution(
        knob, "jnp",
        "auto never picks pallas; "
        + ("mesh run — GSPMD partitions the jnp primitives"
           if mesh is not None else "the fused single program"))


def _log_resolution(knob: str, backend: str, reason: str) -> str:
    """One INFO line per resolution (i.e. once per learner/store —
    make_fns resolves once): ``auto`` silently landing on ``jnp`` under
    a mesh once confused a bench comparison, so the resolved backend
    and why are in the run log."""
    log.info("fused_kernel: %s -> %s (%s)", knob, backend, reason)
    return backend


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
# elementwise epilogue ops on the already-gathered tile, traced into the
# pallas scatter kernel like the rest of row_epilogue.
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


# ------------------------------------------------------------- backends
@names.leg(names.GATHER)
def gather_rows(table: jnp.ndarray, slots: jnp.ndarray,
                backend: str = "jnp") -> jnp.ndarray:
    """ONE fused-row gather of the batch's sorted unique slots.

    The jnp form is the kernel contract every backend must match: the
    store guarantees sorted unique slots with ascending out-of-bounds
    padding (pad_slots_oob), the flags let XLA skip duplicate handling
    (~20% off the fused step, updaters/sgd_updater.py), and padded
    lanes read zeros (mode=fill)."""
    if backend == "pallas" and table.ndim == 2:
        return _pallas_gather(table, slots)
    return table.at[slots].get(indices_are_sorted=True,
                               unique_indices=True,
                               mode="fill", fill_value=0)


@names.leg(names.SCATTER)
def scatter_rows(table: jnp.ndarray, slots: jnp.ndarray,
                 rows: jnp.ndarray, backend: str = "jnp") -> jnp.ndarray:
    """Write ``rows`` back at ``slots`` (padded OOB entries dropped)."""
    if backend == "pallas" and table.ndim == 2:
        return _pallas_scatter(table, slots, rows)
    return table.at[slots].set(rows, indices_are_sorted=True,
                               unique_indices=True, mode="drop")


def _tile_rows(u: int) -> int:
    for r in (_TILE_ROWS, 4, 2, 1):
        if u % r == 0:
            return r
    return 1  # pragma: no cover - unreachable (1 divides everything)


def _pallas_gather(table: jnp.ndarray, slots: jnp.ndarray) -> jnp.ndarray:
    """Row-gather kernel: scalar-prefetched slots drive R async row DMAs
    per grid step from the HBM table into the VMEM output tile; OOB pad
    lanes are zero-filled in VMEM (the mode=fill contract)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, W = table.shape
    u = slots.shape[0]
    R = _tile_rows(u)

    def kern(slots_ref, tbl_ref, out_ref, sems):
        i = pl.program_id(0)
        base = i * R
        for j in range(R):
            s = slots_ref[base + j]

            @pl.when(s < C)
            def _(j=j, s=s):
                pltpu.make_async_copy(tbl_ref.at[s], out_ref.at[j],
                                      sems.at[j]).start()

            @pl.when(jnp.logical_not(s < C))
            def _(j=j):
                out_ref[j, :] = jnp.zeros((W,), out_ref.dtype)
        for j in range(R):
            s = slots_ref[base + j]

            @pl.when(s < C)
            def _(j=j, s=s):
                pltpu.make_async_copy(tbl_ref.at[s], out_ref.at[j],
                                      sems.at[j]).wait()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(u // R,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((R, W), lambda i, s: (i, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA((R,))],
    )
    return jaxtrace.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((u, W), table.dtype),
        interpret=interpret_mode())(slots, table)


def _pallas_scatter(table: jnp.ndarray, slots: jnp.ndarray,
                    rows: jnp.ndarray) -> jnp.ndarray:
    """Plain row scatter-back (no epilogue): the write half of
    :func:`fm_update_rows`, kept separate for apply_count-style
    callers. Table is aliased in place (input_output_aliases)."""
    return _scatter_epilogue(table, slots, rows, extras=(),
                             epilogue=None)


def fm_update_rows(table: jnp.ndarray, slots: jnp.ndarray,
                   rows: jnp.ndarray, gw: jnp.ndarray,
                   gV: jnp.ndarray, vmask: jnp.ndarray,
                   epilogue: Callable, backend: str = "jnp"
                   ) -> jnp.ndarray:
    """The fused scatter-update: run ``epilogue(rows, gw, gV, vmask)``
    — the per-row FTRL/AdaGrad update (updaters.sgd_updater
    row_epilogue, single-sourced so backends cannot drift) — and write
    the result back at ``slots``.

    jnp backend: epilogue in XLA + one scatter. pallas backend: the
    epilogue is traced INTO the scatter kernel and applied per R-row
    VMEM tile before the row DMAs write back — the "update folds into
    the kernel epilogue" half of ISSUE 13."""
    if backend == "pallas" and table.ndim == 2:
        u = slots.shape[0]
        extras = (gw.reshape(u, 1), gV,
                  vmask.reshape(u, 1))

        def tile_epilogue(rows_t, gw_t, gv_t, vm_t):
            return epilogue(rows_t, gw_t[:, 0], gv_t, vm_t[:, 0])

        # one kernel does the epilogue and the write-back: it counts
        # under the write-back's leg
        with names.scope(names.SCATTER):
            return _scatter_epilogue(table, slots, rows, extras,
                                     tile_epilogue)
    new = epilogue(rows, gw, gV, vmask)
    return scatter_rows(table, slots, new, backend="jnp")


def _scatter_epilogue(table: jnp.ndarray, slots: jnp.ndarray,
                      rows: jnp.ndarray, extras: tuple,
                      epilogue: Optional[Callable]) -> jnp.ndarray:
    """Shared pallas scatter kernel: per grid step, compute the new
    R-row tile (``epilogue`` over the rows tile + per-row ``extras``
    blocks, or the rows verbatim) into VMEM scratch, then DMA each
    in-bounds row back to its HBM table slot. The table input aliases
    the output, so the update is in place — composed with the jit-level
    ``donate_argnums`` the step already declares."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, W = table.shape
    u = slots.shape[0]
    R = _tile_rows(u)
    n_extra = len(extras)

    def kern(*refs):
        slots_ref = refs[0]
        rows_ref = refs[1]
        extra_refs = refs[2:2 + n_extra]
        tbl_ref = refs[2 + n_extra]      # aliased input (unused: the
        del tbl_ref                      # DMA targets the out ref)
        out_ref = refs[3 + n_extra]
        scratch, sems = refs[4 + n_extra], refs[5 + n_extra]
        i = pl.program_id(0)
        base = i * R
        if epilogue is None:
            scratch[...] = rows_ref[...]
        else:
            scratch[...] = epilogue(rows_ref[...],
                                    *(r[...] for r in extra_refs))
        for j in range(R):
            s = slots_ref[base + j]

            @pl.when(s < C)
            def _(j=j, s=s):
                pltpu.make_async_copy(scratch.at[j], out_ref.at[s],
                                      sems.at[j]).start()
        for j in range(R):
            s = slots_ref[base + j]

            @pl.when(s < C)
            def _(j=j, s=s):
                pltpu.make_async_copy(scratch.at[j], out_ref.at[s],
                                      sems.at[j]).wait()

    in_specs = [pl.BlockSpec((R, W), lambda i, s: (i, 0))]
    for e in extras:
        w_e = e.shape[1]
        in_specs.append(pl.BlockSpec((R, w_e), lambda i, s: (i, 0)))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))   # table
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(u // R,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((R, W), table.dtype),
                        pltpu.SemaphoreType.DMA((R,))],
    )
    # operand order: slots(0) rows(1) extras(2..) table(last) — the
    # alias key counts every operand including the scalar prefetch
    return jaxtrace.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, W), table.dtype),
        input_output_aliases={2 + n_extra: 0},
        interpret=interpret_mode())(slots, rows, *extras, table)
