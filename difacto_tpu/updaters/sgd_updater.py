"""SGD updater: FTRL for w, AdaGrad for V, over a fixed-capacity slot table.

TPU-native re-design of the reference's server-side SGDUpdater
(src/sgd/sgd_updater.{h,cc}). The per-feature hash map of SGDEntry records
(sgd_updater.h:20-69) becomes a struct-of-arrays slot table in device memory;
per-key scalar updates (sgd_updater.cc:105-152) become vectorised gather ->
elementwise -> scatter over the batch's unique slots. **Row 0 is a reserved
trash slot**: padded/invalid entries scatter there, so every kernel runs
unconditionally with static shapes.

Exact semantics preserved:

- FTRL-proximal w update (UpdateW, sgd_updater.cc:105-131): g += l2*w;
  n' = sqrt(n^2 + g^2); z -= g - (n' - n)/lr * w; w = soft-threshold(z, l1)
  scaled by lr/(lr_beta + n').
- AdaGrad V update (UpdateV, sgd_updater.cc:133-142) with V_l2, applied only
  to rows whose embedding was *pulled* this batch (lens[i] > 1 semantics,
  sgd_updater.cc:91-96).
- Lazy V activation (InitV triggers, sgd_updater.cc:71-74,123-127): the union
  of the reference's two trigger sites is exactly
  ``v_live |= (w != 0) & (cnt > V_threshold)`` re-evaluated after every count
  or gradient update. V rows are pre-filled with the uniform init
  ``(u01 - 0.5) * V_init_scale`` (InitV, sgd_updater.cc:144-152) at state
  creation — activation just flips the flag. (Deviation: init values come
  from a counter-based PRNG per slot, not the reference's call-order-dependent
  rand_r stream; distribution is identical.)
- Pull gating (Get, sgd_updater.cc:34-58): the embedding is served only when
  live and not suppressed by ``l1_shrk`` (w == 0).
- Evaluate (sgd_updater.cc:15-32): penalty uses **l2 for the V term as well**
  (a reference quirk — UpdateV regularises with V_l2 but Evaluate charges
  l2); nnz counts V_dim for every live embedding regardless of w.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Param
from ..losses.fm import FMParams, code_rows, packs_codes
from ..obs import names

TRASH_SLOT = 0  # row 0 absorbs padded scatters; never a real feature


@dataclass
class SGDUpdaterParam(Param):
    l1: float = field(default=1.0, metadata=dict(lo=0, hi=1e10))
    l2: float = field(default=0.0, metadata=dict(lo=0, hi=1e10))
    V_l2: float = field(default=0.01, metadata=dict(lo=0, hi=1e10))
    lr: float = field(default=0.01, metadata=dict(lo=0, hi=10))
    lr_beta: float = field(default=1.0, metadata=dict(lo=0, hi=1e10))
    V_lr: float = field(default=0.01, metadata=dict(lo=0, hi=1e10))
    V_lr_beta: float = field(default=1.0, metadata=dict(lo=0, hi=10))
    V_init_scale: float = field(default=0.01, metadata=dict(lo=0, hi=10))
    V_dim: int = field(default=0, metadata=dict(lo=0))
    V_threshold: int = 10
    l1_shrk: bool = True
    seed: int = 0
    # > 0 switches the store to a fixed-capacity hashed table: slot =
    # reversed_id mod (capacity-1) + 1, no host dictionary. Deterministic
    # across hosts (multi-controller requirement, parallel/multihost.py);
    # collisions alias features, the standard hashing-trick tradeoff.
    hash_capacity: int = 0
    # dictionary store only: initial slot-table rows (grows by doubling,
    # store/local.py). Lower it to bound the first HBM allocation on
    # small models — or, in tests, to force growth events.
    init_capacity: int = field(default=1 << 14, metadata=dict(lo=2))
    # storage dtype of the fused [V | Vg] embedding rows. bfloat16 halves
    # the dominant HBM traffic of the fused step (the [U, 2k] row
    # gather/scatter); compute stays float32. FTRL scalars (w/z/sqrt_g)
    # always stay float32 — z accumulates and must not round.
    V_dtype: str = field(default="float32",
                         metadata=dict(enum=["float32", "bfloat16"]))
    # pad each VVg half to a multiple of 64 elements so the fused row is a
    # multiple of the 128-lane TPU tile width. Sub-lane-width rows make the
    # per-row table scatter a misaligned read-modify-write: at V_dim=16
    # over a 4.2M-row table, the [196k, 32] scatter measured 33 ms vs
    # 15 ms for the padded [196k, 128] row — MORE bytes, half the
    # time. The pad costs up to 4x VVg HBM at V_dim<=32,
    # so it auto-disables when the padded table would exceed
    # ``pad_v_rows_max_mb`` (the donated-state double plus the batch
    # cache must still fit; an 8.4M-row V16 bf16 table OOMed a 16 GB
    # chip padded but trains unpadded). Set pad_v_rows=False to force
    # the compact layout.
    pad_v_rows: bool = True
    pad_v_rows_max_mb: int = 1536
    # ---- table-capacity levers (difacto_tpu/capacity/). All default
    # OFF: fp32 + admit-all + no tier is
    # byte-identical to the pre-capacity trajectory.
    # Storage dtype of the fused slot rows. "fp32" = full precision (the
    # container still follows the legacy V_dtype knob, so existing bf16
    # configs are untouched); "bf16" forces the bfloat16 container;
    # "int8"/"fp8" store BOTH embedding halves as 8-bit codes in an int8
    # container with per-row f32 scale factors riding the spare scalar
    # lanes — 4x (2x vs bf16) more rows per HBM byte, with dequant/
    # requant folded into the fused row epilogue so the hot path stays
    # one gather + one scatter (ops/fused.quant_half). V_dim > 0 only
    # (the flat layout has no fused row to quantize).
    slot_dtype: str = field(default="fp32",
                            metadata=dict(enum=["fp32", "bf16",
                                                "int8", "fp8"]))
    # Frequency-adaptive admission (capacity/sketch.py): a hashed token
    # must reach this count-min-sketch estimate in the producer's ingest
    # stream before it is admitted to the table; rarer tokens route to
    # an OOB lane (gathers zeros, scatter dropped). 0 = admit all. The
    # TPU-side analog of the reference's frequency filter: rare features
    # never cost a slot.
    admit_min_count: int = field(default=0, metadata=dict(lo=0))
    # Occupancy-pressure eviction (SlotStore.maybe_evict, cold path):
    # when the occupied fraction of table rows exceeds this threshold,
    # the lowest-count rows are evicted (demoted to the cold tier when
    # it is on, else their FTRL/AdaGrad scalars reset to virgin) until
    # occupancy drops to 0.9x the threshold. 0 = off.
    evict_occupancy: float = field(default=0.0, metadata=dict(lo=0, hi=1))
    # Host-RAM cold tier (capacity/tier.py): the device table holds
    # hash_capacity - cold_tier_rows HOT rows; the zipf tail lives in
    # host RAM and rows promote/demote in batches on the dispatch
    # thread. 0 = off. Hashed stores with V_dim > 0 only.
    cold_tier_rows: int = field(default=0, metadata=dict(lo=0))


class SGDState(NamedTuple):
    """Slot-table model state; all arrays have capacity+1 rows (row 0 trash).

    TWO layouts, keyed on V_dim:

    - ``V_dim == 0`` (linear models): flat f32 FTRL arrays w/z/sqrt_g/cnt
      (+ v_live, vestigial), ``VVg`` is [C, 0]. The flat T(1024) scalar
      layout is the fast form when there is no embedding row to ride.
    - ``V_dim > 0``: EVERYTHING lives in ``VVg`` [C, Wx] and the five
      flat fields are empty [0] placeholders (pytree/donation still sees
      six leaves). The row is [V | pad | Vg | pad | scal]: V in [:, :k],
      Vg in [:, h:h+k] with h = v_half(param) >= k, and the last SCAL_W
      lanes carry the FTRL scalars (w, z, sqrt_g, cnt as f32 bit-split
      into storage-dtype lane pairs — see pack_scal) plus the v_live
      flag. One fused row means the step runs ONE gather + ONE scatter
      instead of ~10 per-slot table ops; each op costs ~10-19 ns per
      ROW regardless of width, so merging ops is the lever (measured
      52.4 -> 37.4 ms for the u=262k V64 table-op train, 31.0 -> 21.0 ms
      for u=196k V16 where the scalars ride the EXISTING pad lanes).

    Reference analog: the SGDEntry record (src/sgd/sgd_updater.h:20-69)
    keeps w, z, sqrt_g and V[] contiguous per feature for the same
    reason — one cache line per key.
    """
    w: jnp.ndarray        # f32[C] (V_dim=0) | f32[0]
    z: jnp.ndarray        # f32[C] FTRL dual  | f32[0]
    sqrt_g: jnp.ndarray   # f32[C] FTRL accumulated grad norm | f32[0]
    cnt: jnp.ndarray      # f32[C] feature occurrence counts  | f32[0]
    VVg: jnp.ndarray      # [C, Wx] fused rows (V_dim>0) | [C, 0]
    v_live: jnp.ndarray   # bool[C] (V_dim=0, vestigial) | bool[0]

    @property
    def capacity(self) -> int:
        return self.VVg.shape[0]


def quantized(param: SGDUpdaterParam) -> bool:
    """True when the fused rows store 8-bit codes with per-row scales
    (slot_dtype int8/fp8) — the layout where the embedding halves need a
    dequant before use and a requant on write-back."""
    return param.slot_dtype in ("int8", "fp8") and param.V_dim > 0


def v_dtype(param: SGDUpdaterParam):
    """Container dtype of the fused rows. slot_dtype=fp32 means "full
    precision" and defers to the legacy V_dtype knob (so existing bf16
    configs keep their exact layout); int8 AND fp8 share the int8
    container (fp8 bit patterns bitcast in, ops/fused.quant_half)."""
    if param.V_dim > 0:
        if param.slot_dtype in ("int8", "fp8"):
            return jnp.int8
        if param.slot_dtype == "bf16":
            return jnp.bfloat16
    return jnp.bfloat16 if param.V_dtype == "bfloat16" else jnp.float32


def v_half(param: SGDUpdaterParam, capacity: int) -> int:
    """Stored width of each VVg half at this table capacity: V_dim
    rounded up to a multiple of 64 (so the fused [V | Vg] row is a
    multiple of the 128-lane tile) when pad_v_rows and the padded table
    fits pad_v_rows_max_mb, else exactly V_dim. The full row adds the
    scalar lanes behind the halves — row_layout is the single source for
    the complete geometry."""
    k = param.V_dim
    if k == 0 or not param.pad_v_rows:
        return k
    h = -(-k // 64) * 64
    bytes_per_el = np.dtype(v_dtype(param)).itemsize
    if capacity * 2 * h * bytes_per_el > param.pad_v_rows_max_mb << 20:
        return k
    return h


def fuse_vvg(V, Vg, h: int):
    """The padded embedding halves: [V | pad | Vg | pad] with each half
    zero-padded from k columns to h. Accepts jnp or numpy halves. The
    fused-row builders below append the scalar lanes behind this."""
    k = V.shape[1]
    if h == k:
        return jnp.concatenate([V, Vg], axis=1)
    pad = jnp.zeros((V.shape[0], h - k), dtype=jnp.asarray(V).dtype)
    return jnp.concatenate([V, pad, Vg, pad], axis=1)


# fused-row scalar section: the BYTES of f32[8] = (w, z, sqrt_g, cnt,
# v_live-as-1.0/0.0, scale_V, scale_Vg, 1 spare) reinterpreted in the
# row's storage dtype — 8 f32 lanes, 16 bfloat16 lanes, or 32 int8 lanes
# (quantized slots). One contiguous minor-dim slice plus a bulk
# bitcast_convert_type reads/writes the whole section (bit-exact: each
# f32 spans 4/itemsize adjacent lanes, low bits first), which keeps XLA
# on the row-major layout — per-lane extraction with uint shifts made
# layout assignment prefer a TRANSPOSED gather and insert a full-table
# copy of the donated state every step. Lanes 5/6
# carry the per-row quantization scales of the V/Vg halves when
# slot_dtype is int8/fp8 (ops/fused.quant_half); exact 0.0 otherwise —
# bit-identical to the old spare-lane zeros.
SCAL_F32S = 8


def scal_lanes(dtype) -> int:
    return SCAL_F32S * (4 // np.dtype(dtype).itemsize)


def row_layout(param: SGDUpdaterParam, capacity: int
               ) -> Tuple[int, int, int, int]:
    """(k, h, Wx, off) of the fused row at this capacity: half width h
    from v_half (budget-gated lane padding), total row width Wx, and the
    scalar-section offset off = Wx - scal_lanes. The scalars ride INSIDE
    the Vg-half pad when it is wide enough (V_dim <= 48 padded: zero
    extra bytes); otherwise the row is extended to the next multiple of
    the 128-lane tile (V_dim=64 bf16: 128 -> 256). The multiple is
    load-bearing: a 192-lane row made XLA's entry-layout pass choose a
    TRANSPOSED {0,1} table layout (it avoids the 192->256 tile padding),
    which inserted two full-table transpose copies around every step's
    gather/scatter — ~5.7 ms/step of pure copy at 2M rows. A tile-aligned
    width costs the same HBM as the padded 192 and keeps {1,0}."""
    k = param.V_dim
    assert k > 0, "flat layout has no fused row"
    h = v_half(param, capacity)
    ns = scal_lanes(v_dtype(param))
    Wx = 2 * h if h - k >= ns else -(-(2 * h + ns) // 128) * 128
    return k, h, Wx, Wx - ns


def pack_scal(w, z, sqrt_g, cnt, live, dtype, scale_V=None, scale_Vg=None):
    """f32 scalar columns + bool live -> [n, scal_lanes] of ``dtype``.
    ``scale_V``/``scale_Vg`` fill the quantization-scale lanes 5/6
    (quantized slots); omitted they stay exact 0.0 — byte-identical to
    the historical spare-lane zeros."""
    wf = jnp.asarray(w, jnp.float32)
    f = jnp.stack([wf, jnp.asarray(z, jnp.float32),
                   jnp.asarray(sqrt_g, jnp.float32),
                   jnp.asarray(cnt, jnp.float32),
                   jnp.asarray(live, jnp.float32),
                   jnp.zeros_like(wf) if scale_V is None
                   else jnp.asarray(scale_V, jnp.float32),
                   jnp.zeros_like(wf) if scale_Vg is None
                   else jnp.asarray(scale_Vg, jnp.float32),
                   jnp.zeros_like(wf)],
                  axis=1)
    if dtype == jnp.float32:
        return f
    n_per = 4 // np.dtype(dtype).itemsize
    return jax.lax.bitcast_convert_type(f, dtype).reshape(
        f.shape[0], n_per * SCAL_F32S)


def scal_f32(lanes):
    """[n, scal_lanes] scalar section (any container dtype) -> the
    underlying f32[n, SCAL_F32S] matrix — columns (w, z, sqrt_g, cnt,
    live, scale_V, scale_Vg, spare)."""
    if lanes.dtype == jnp.float32:
        return lanes
    n_per = 4 // np.dtype(lanes.dtype).itemsize
    return jax.lax.bitcast_convert_type(
        lanes.reshape(lanes.shape[0], SCAL_F32S, n_per), jnp.float32)


def unpack_scal(lanes):
    """[n, scal_lanes] scalar section -> (w, z, sqrt_g, cnt, live)."""
    f = scal_f32(lanes)
    return f[:, 0], f[:, 1], f[:, 2], f[:, 3], f[:, 4] > 0


def scal_cols(param: SGDUpdaterParam, state: SGDState):
    """(w, z, sqrt_g, cnt, v_live) as full-table columns — the host /
    eval / checkpoint view, layout-independent. Column slices of the
    fused rows read whole tiles, so this is a full-table pass: fine once
    per epoch or task, never per step."""
    if param.V_dim == 0:
        return state.w, state.z, state.sqrt_g, state.cnt, state.v_live
    _, _, _, off = row_layout(param, state.capacity)
    return unpack_scal(state.VVg[:, off:])


def col_w(param: SGDUpdaterParam, state: SGDState) -> jnp.ndarray:
    return scal_cols(param, state)[0]


def col_V(param: SGDUpdaterParam, state: SGDState) -> jnp.ndarray:
    """Full-table V columns (storage dtype), pad/scal lanes stripped."""
    if param.V_dim == 0:
        return state.VVg
    k, _, _, _ = row_layout(param, state.capacity)
    return state.VVg[:, :k]


def col_Vg(param: SGDUpdaterParam, state: SGDState) -> jnp.ndarray:
    if param.V_dim == 0:
        return state.VVg
    k, h, _, _ = row_layout(param, state.capacity)
    return state.VVg[:, h:h + k]


def emb_cols_f32(param: SGDUpdaterParam, state: SGDState):
    """Full-table LOGICAL f32 (V, Vg) columns — dequantized when the
    rows store 8-bit codes (the per-row scales come from the scalar
    lanes). The layout-independent view checkpoints, eval and growth
    re-layout read; full-table pass, cold paths only."""
    k, h, _, off = row_layout(param, state.capacity)
    V, Vg = state.VVg[:, :k], state.VVg[:, h:h + k]
    if not quantized(param):
        return V.astype(jnp.float32), Vg.astype(jnp.float32)
    from ..ops import fused
    f = scal_f32(state.VVg[:, off:])
    return (fused.dequant_half(V, f[:, 5], param.slot_dtype),
            fused.dequant_half(Vg, f[:, 6], param.slot_dtype))


def state_bytes(param: SGDUpdaterParam, capacity: int) -> int:
    """HBM bytes of the slot table at ``capacity`` rows — the number the
    fs-sharding capacity story is about: per-device residency is
    ``state_bytes / fs`` (parallel/mesh.py fs_shard_bounds), so an
    fs-way mesh holds an fs-times-larger table in the same per-chip
    HBM. One definition shared by parallel/capacity.py's legs and the
    store's shard stats."""
    if param.V_dim == 0:
        # four f32 columns (w, z, sqrt_g, cnt) + bool v_live
        return capacity * (4 * 4 + 1)
    _, _, Wx, _ = row_layout(param, capacity)
    return capacity * Wx * np.dtype(v_dtype(param)).itemsize


def gather_bytes(param: SGDUpdaterParam, capacity: int, u_cap: int,
                 training: bool = False) -> int:
    """HBM bytes the PULL of ``u_cap`` unique rows moves at this table
    capacity's row layout — with :func:`scatter_bytes` the per-dispatch
    unit of the ``store_gather_bytes_total`` counter
    (docs/observability.md): serve counts the pull once per dispatch,
    train the pull and the push, so cross-shard row traffic is
    observable per path.

    A fused-row table (``V_dim > 0``) pulls each row once, whole, and
    the step threads it to the push. The flat table (``V_dim = 0``) has
    no row: a predict step gathers ``w`` alone, and a ``training`` step
    three float32 scalars a slot, ``w``, ``sqrt_g`` and ``z``. (Its
    text asks for ``w`` twice, in ``get_rows`` for the forward and again
    in ``apply_grad`` for the push's FTRL; the two gathers are the same
    operation on the same operand and XLA merges them, on the CPU and
    for the TPU alike: tests/test_flat_table.py counts the compiled
    program's.)"""
    if param.V_dim == 0:
        return u_cap * (3 if training else 1) * 4
    _, _, Wx, _ = row_layout(param, capacity)
    return u_cap * Wx * np.dtype(v_dtype(param)).itemsize


def scatter_bytes(param: SGDUpdaterParam, capacity: int, u_cap: int) -> int:
    """HBM bytes the PUSH of ``u_cap`` unique rows writes back: the
    fused row whole, or the flat table's ``w``, ``sqrt_g`` and ``z``
    (``cnt`` moves only with the count push of epoch 0, which is not a
    step's traffic)."""
    if param.V_dim == 0:
        return u_cap * 3 * 4
    return gather_bytes(param, capacity, u_cap)


def set_all_live(param: SGDUpdaterParam, state: SGDState) -> SGDState:
    """Bench/entry helper: activate every embedding row."""
    if param.V_dim == 0:
        return state._replace(v_live=jnp.ones_like(state.v_live))
    _, _, _, off = row_layout(param, state.capacity)
    f = scal_f32(state.VVg[:, off:])
    scal = pack_scal(f[:, 0], f[:, 1], f[:, 2], f[:, 3],
                     jnp.ones_like(f[:, 0], bool), state.VVg.dtype,
                     scale_V=f[:, 5], scale_Vg=f[:, 6])
    return state._replace(
        VVg=jnp.concatenate([state.VVg[:, :off], scal], axis=1))


def build_rows(param: SGDUpdaterParam, capacity: int, V, Vg,
               w, z, sqrt_g, cnt, live) -> jnp.ndarray:
    """Assemble full fused rows [V | pad | Vg | pad | scal] at this
    capacity's layout from f32 parts. Every builder (init, growth
    re-layout, checkpoint assembly) goes through here so the layout
    cannot drift between sites."""
    _, h, Wx, off = row_layout(param, capacity)
    dt = v_dtype(param)
    if quantized(param):
        from ..ops import fused
        Vc, sV = fused.quant_half(jnp.asarray(V, jnp.float32),
                                  param.slot_dtype)
        Vgc, sVg = fused.quant_half(jnp.asarray(Vg, jnp.float32),
                                    param.slot_dtype)
        halves = fuse_vvg(Vc, Vgc, h)
    else:
        sV = sVg = None
        halves = fuse_vvg(jnp.asarray(V, jnp.float32),
                          jnp.asarray(Vg, jnp.float32), h).astype(dt)
    scal = pack_scal(jnp.asarray(w, jnp.float32), jnp.asarray(z, jnp.float32),
                     jnp.asarray(sqrt_g, jnp.float32),
                     jnp.asarray(cnt, jnp.float32),
                     jnp.asarray(live), dt, scale_V=sV, scale_Vg=sVg)
    # in-pad layout (off < 2h): the scal section replaces the tail of the
    # Vg-half pad; appended layout: zero gap lanes between halves and scal
    if off <= 2 * h:
        return jnp.concatenate([halves[:, :off], scal], axis=1)
    gap = jnp.zeros((halves.shape[0], off - 2 * h), dt)
    return jnp.concatenate([halves, gap, scal], axis=1)


# Rows a block of the quantized table's full-table cold passes
# (init_state, evaluate). A float32 view of a whole 8-bit table is four
# times the codes (2^25 x 64 V_dim: 8.59 GB beside 8.59 GB of rows), and
# the scalar lanes' bitcast over the whole table makes two 4.29 GB
# temporaries on the v5e; a block's are 1/512 of that.
QUANT_BLOCK_ROWS = 1 << 16


def row_blocks(capacity: int) -> Tuple[int, int]:
    """(rows a block, blocks) that tile ``capacity`` rows exactly: the
    largest power of two up to QUANT_BLOCK_ROWS that divides it."""
    r = int(np.gcd(capacity, QUANT_BLOCK_ROWS))
    return r, capacity // r


def uniform_rows(key, first, rows: int, cols: int) -> jnp.ndarray:
    """Rows ``first .. first + rows`` of ``jax.random.uniform(key,
    (capacity, cols))``, bit for bit, without drawing the rest: the
    partitionable threefry (JAX's default) makes element ``i`` of the
    flattened draw from the counter pair ``(i >> 32, i & 0xffffffff)``
    alone, so a block is its own counters through the same hash and the
    same mantissa fill (``jax._src.random._uniform``). ``first`` may be
    traced; the table's ``capacity * cols`` must stay below 2^32 (the
    high counter word is then 0), and ``jax_threefry_partitionable``
    on (the caller checks both)."""
    from jax.extend.random import threefry2x32_p
    base = jnp.asarray(first, jnp.uint32) * jnp.uint32(cols)
    lo = base + jax.lax.iota(jnp.uint32, rows * cols).reshape(rows, cols)
    b1, b2 = threefry2x32_p.bind(key[0], key[1], jnp.zeros_like(lo), lo)
    bits = jax.lax.shift_right_logical(b1 ^ b2, jnp.uint32(32 - 23))
    # jax's [0, 1) rescale (``* 1 + 0``, ``max(0, .)``) is exact here
    return jax.lax.bitcast_convert_type(
        bits | jnp.uint32(0x3F800000), jnp.float32) - jnp.float32(1.0)


def init_state(param: SGDUpdaterParam, capacity: int,
               blocked: bool = True) -> SGDState:
    """The seed's table. 8-bit rows are built ``blocked`` (one
    ``lax.map`` over :func:`row_blocks`, the same codes as
    :func:`build_rows` over the whole draw), except where a sharded
    store asks for the whole-table form (``store/local._jitted_init``:
    GSPMD partitions that draw by shard)."""
    k = param.V_dim
    if k == 0:
        def zeros():
            # distinct buffers — donate_argnums forbids aliased leaves
            return jnp.zeros(capacity, dtype=jnp.float32)
        return SGDState(
            w=zeros(), z=zeros(), sqrt_g=zeros(), cnt=zeros(),
            VVg=jnp.zeros((capacity, 0), jnp.float32),
            v_live=jnp.zeros(capacity, dtype=bool))
    key = jax.random.PRNGKey(param.seed)
    _, _, Wx, _ = row_layout(param, capacity)
    if quantized(param):
        # quantized rows need their per-row V scale in the scalar lanes
        # (a zero scale would dequantize the init values to 0), so init
        # routes through the full row builder
        def rows(V):
            n = V.shape[0]
            zcol = jnp.zeros(n, jnp.float32)
            return build_rows(param, capacity, V,
                              jnp.zeros((n, k), jnp.float32),
                              zcol, zcol, zcol, zcol,
                              jnp.zeros(n, dtype=bool))
        if (blocked and capacity * k < 1 << 32
                and jax.config.jax_threefry_partitionable):
            R, nb = row_blocks(capacity)
            T = jax.lax.map(
                lambda i: rows((uniform_rows(key, i * R, R, k) - 0.5)
                               * param.V_init_scale),
                jnp.arange(nb, dtype=jnp.int32)).reshape(capacity, Wx)
        else:
            T = rows((jax.random.uniform(key, (capacity, k),
                                         dtype=jnp.float32) - 0.5)
                     * param.V_init_scale)
    else:
        V = (jax.random.uniform(key, (capacity, k), dtype=jnp.float32)
             - 0.5) * param.V_init_scale
        # all-zero scalar lanes already encode (w,z,sqrt_g,cnt,live) =
        # (0,0,0,0,False) in both dtypes, so only the V block needs
        # writing
        T = jnp.zeros((capacity, Wx), v_dtype(param)
                      ).at[:, :k].set(V.astype(v_dtype(param)))
    empty = jnp.zeros(0, jnp.float32)
    return SGDState(w=empty, z=empty + 0, sqrt_g=empty + 0, cnt=empty + 0,
                    VVg=T, v_live=jnp.zeros(0, dtype=bool))


def grow_state(param: SGDUpdaterParam, state: SGDState, new_capacity: int
               ) -> SGDState:
    """Double-and-copy growth; new V rows get fresh init values. Growth
    can cross the pad_v_rows_max_mb threshold, shrinking v_half back to
    V_dim — old rows are re-laid-out to the new row width (their scalar
    lanes move with the scal offset)."""
    old = state.capacity
    if new_capacity <= old:
        return state
    ext = init_state(param, new_capacity)
    # compare the FULL geometry, not the width: crossing the
    # pad_v_rows_max_mb gate at V_dim<=48 keeps Wx=128 while h moves
    # (64 -> k), so a width-equality guard would silently leave Vg at
    # the old offset (advisor round-5 finding, reproduced: grown rows
    # read Vg=0 from the old V-pad lanes)
    if param.V_dim and row_layout(param, old) != row_layout(param,
                                                            new_capacity):
        k, h, _, off = row_layout(param, old)
        w, z, sg, cnt, live = unpack_scal(state.VVg[:, off:])
        Vf, Vgf = emb_cols_f32(param, state)
        state = state._replace(VVg=build_rows(
            param, new_capacity, Vf, Vgf, w, z, sg, cnt, live))
    return SGDState(*(jnp.concatenate([a, jnp.asarray(b)[old:]], axis=0)
                      for a, b in zip(state, ext)))


def ftrl_w(w, z, sg, gw, l1: float, l2: float, lr: float, lr_beta: float):
    """The FTRL-proximal w update (UpdateW, sgd_updater.cc:105-131),
    identical math in both layouts (flat ``V_dim = 0`` arrays and
    fused rows)."""
    g = gw + l2 * w
    sg_new = jnp.sqrt(sg * sg + g * g)
    z_new = z - (g - (sg_new - sg) / lr * w)
    eta = (lr_beta + sg_new) / lr
    w_new = jnp.where(
        jnp.abs(z_new) <= l1, 0.0,
        (z_new - jnp.sign(z_new) * l1) / eta)
    return w_new, z_new, sg_new


@names.leg(names.UPDATE)
def row_epilogue(param: SGDUpdaterParam, capacity: int, rows: jnp.ndarray,
                 gw: jnp.ndarray, gV: Optional[jnp.ndarray],
                 pull_vmask: Optional[jnp.ndarray]) -> jnp.ndarray:
    """The per-row FTRL(w) + AdaGrad(V) update on gathered fused rows
    [n, Wx] -> new rows, WITHOUT the surrounding gather/scatter: the
    single source of the push math (apply_grad_rows scatters its
    result). ``pull_vmask`` gates AdaGrad to rows whose
    embedding was PULLED this batch (lens[i] > 1 semantics,
    sgd_updater.cc:91-96); padded OOB lanes compute garbage that the
    scatter drops."""
    k, h, _, off = row_layout(param, capacity)
    thr = float(param.V_threshold)
    q = quantized(param)
    f = scal_f32(rows[:, off:])
    w, z, sg, cnt, live = f[:, 0], f[:, 1], f[:, 2], f[:, 3], f[:, 4] > 0
    # per-row quantization scales ride lanes 5/6 (exact 0.0 when the
    # rows are not quantized — carried through bit-identically)
    sV, sVg = f[:, 5], f[:, 6]
    w_new, z_new, sg_new = ftrl_w(w, z, sg, gw, param.l1, param.l2,
                                  param.lr, param.lr_beta)
    # lazy-V activation on the touched rows (the union of the
    # reference's two trigger sites re-evaluated after the update)
    live_new = live | ((w_new != 0) & (cnt > thr))

    if gV is not None:
        if q:
            from ..ops import fused
            V = fused.dequant_half(rows[:, :k], sV, param.slot_dtype)
            Vg = fused.dequant_half(rows[:, h:h + k], sVg, param.slot_dtype)
        else:
            V = rows[:, :k].astype(jnp.float32)
            Vg = rows[:, h:h + k].astype(jnp.float32)
        gv = gV + param.V_l2 * V
        Vg_new = jnp.sqrt(Vg * Vg + gv * gv)
        V_new = V - param.V_lr / (Vg_new + param.V_lr_beta) * gv
        # AdaGrad only touches rows whose embedding was PULLED this
        # batch (lens[i] > 1 semantics, sgd_updater.cc:91-96)
        upd = pull_vmask[:, None] > 0
        if q:
            # requant with FRESH per-row scales; both the codes and the
            # scales are gated on pull_vmask so an untouched row keeps a
            # consistent (codes, scale) pair
            Vc, sV_new = fused.quant_half(V_new, param.slot_dtype)
            Vgc, sVg_new = fused.quant_half(Vg_new, param.slot_dtype)
            emb = jnp.where(upd, fuse_vvg(Vc, Vgc, h), rows[:, :2 * h])
            um = pull_vmask > 0
            sV = jnp.where(um, sV_new, sV)
            sVg = jnp.where(um, sVg_new, sVg)
        else:
            emb = jnp.where(upd, fuse_vvg(V_new, Vg_new, h),
                            rows[:, :2 * h].astype(jnp.float32)
                            ).astype(rows.dtype)
    else:
        emb = rows[:, :2 * h]
    scal = pack_scal(w_new, z_new, sg_new, cnt, live_new, rows.dtype,
                     scale_V=sV, scale_Vg=sVg)
    # in-pad layout: scal replaces the tail of emb's own pad lanes;
    # appended layout: the gap lanes between are carried through
    if off <= 2 * h:
        return jnp.concatenate([emb[:, :off], scal], axis=1)
    return jnp.concatenate([emb, rows[:, 2 * h:off], scal], axis=1)


def make_fns(param: SGDUpdaterParam, mesh=None):
    """Build the pure update/get functions with hyperparameters baked in
    as compile-time constants. Returns a namespace of jit-ready callables
    (not yet jit-wrapped; the store/learner composes and jits them).

    ``mesh`` is the store's (None: one device). The functions are plain
    XLA ops that GSPMD partitions under it; the two table legs also take
    a static ``own_cap`` with which, under ``mesh_fs > 1``, each shard
    gathers and scatters only the run of ``slots`` it owns
    (ops/fused.gather_rows). ``own_cap`` is counted by the caller, never
    assumed: a run shorter than the rows a shard owns loses updates."""
    from ..ops import fused

    l1, l2 = param.l1, param.l2
    lr, lr_beta = param.lr, param.lr_beta
    has_V = param.V_dim > 0
    # V_l2 / V_lr / V_lr_beta are read by row_epilogue from ``param``.

    def _gather(arr, slots, own_cap=None):
        # the store guarantees sorted unique slots (map_keys_dedup) with
        # out-of-bounds ASCENDING padding (pad_slots) — the gather-flag
        # contract lives in ops/fused.gather_rows (measured ~20% off
        # the fused step); padded lanes read as zeros (mode=fill)
        return fused.gather_rows(arr, slots, mesh, own_cap)

    def _scatter(arr, slots, rows, own_cap=None):
        # padded (out-of-bounds) entries are dropped, real rows are unique
        return fused.scatter_rows(arr, slots, rows, mesh, own_cap)

    thr = float(param.V_threshold)

    def _layout(state):
        return row_layout(param, state.capacity)

    @names.leg(names.UPDATE)
    def _ftrl(w, z, sg, gw):
        return ftrl_w(w, z, sg, gw, l1, l2, lr, lr_beta)

    def pull_rows(state: SGDState, slots: jnp.ndarray,
                  own_cap: Optional[int] = None) -> jnp.ndarray:
        """ONE full fused-row gather of the batch's unique slots. The
        train step (step.py) threads the result from pull to push so
        the push never re-gathers. A partial-row gather
        (VVg[slots, :k]) would lower to a strided gather ~8x slower. V
        keeps its STORAGE dtype (param.V_dtype) so the loss's
        per-token gather can ride bf16."""
        return _gather(state.VVg, slots, own_cap)

    @names.leg(names.FORWARD)
    def rows_to_params(state: SGDState, rows: jnp.ndarray) -> FMParams:
        """(w, V, v_mask) views of gathered fused rows (Get,
        sgd_updater.cc:34-58): the embedding is served only when live
        and not suppressed by ``l1_shrk`` (w == 0). 8-bit rows also
        hand the forward their codes (losses/fm.code_rows) to gather in
        place of the dequantised V."""
        _, _, _, off = _layout(state)
        f = scal_f32(rows[:, off:])
        w, live = f[:, 0], f[:, 4] > 0
        vmask = live
        if param.l1_shrk:
            vmask = vmask & (w != 0)
        if quantized(param):
            # loss-side V must be real values, not codes: dequantize
            # with the per-row scale riding lane 5 (f32 compute)
            V = fused.dequant_half(rows[:, :param.V_dim], f[:, 5],
                                   param.slot_dtype)
        else:
            V = rows[:, :param.V_dim]
        vmask = vmask.astype(jnp.float32)
        codes = None
        if quantized(param) and packs_codes(param.V_dim):
            codes = code_rows(rows[:, :param.V_dim], w, f[:, 5], vmask,
                              param.slot_dtype)
        return FMParams(w=w, V=V, v_mask=vmask, codes=codes)

    def get_rows(state: SGDState, slots: jnp.ndarray,
                 own_cap: Optional[int] = None) -> FMParams:
        """Pull the [w, V, v_mask] rows of the batch's unique slots
        (Get)."""
        if not has_V:
            return FMParams(w=_gather(state.w, slots))
        return rows_to_params(state, pull_rows(state, slots, own_cap))

    def apply_count(state: SGDState, slots: jnp.ndarray, counts: jnp.ndarray
                    ) -> SGDState:
        """kFeaCount push (Update, sgd_updater.cc:64-75). Sorted unique
        slots with out-of-bounds padding (dropped). Touched rows also
        re-evaluate their lazy-V activation (InitV trigger,
        sgd_updater.cc:71-74) — untouched rows cannot flip, their (w,
        cnt) did not change."""
        if not has_V:
            cnt = state.cnt.at[slots].add(counts, indices_are_sorted=True,
                                          unique_indices=True, mode="drop")
            return state._replace(cnt=cnt)
        _, _, _, off = _layout(state)
        rows = _gather(state.VVg, slots)
        with names.scope(names.UPDATE):
            f = scal_f32(rows[:, off:])
            w, z, sg, cnt, live = (f[:, 0], f[:, 1], f[:, 2], f[:, 3],
                                   f[:, 4] > 0)
            cnt_new = cnt + counts
            live_new = live | ((w != 0) & (cnt_new > thr))
            # scale lanes 5/6 carried through — a count push must not
            # zero a quantized row's dequant scales
            scal = pack_scal(w, z, sg, cnt_new, live_new, state.VVg.dtype,
                             scale_V=f[:, 5], scale_Vg=f[:, 6])
            out = jnp.concatenate([rows[:, :off], scal], axis=1)
        return state._replace(VVg=_scatter(state.VVg, slots, out))

    def apply_grad_rows(state: SGDState, slots: jnp.ndarray,
                        rows: jnp.ndarray, gw: jnp.ndarray,
                        gV: Optional[jnp.ndarray],
                        pull_vmask: Optional[jnp.ndarray],
                        own_cap: Optional[int] = None) -> SGDState:
        """kGradient push over rows the step ALREADY gathered
        (pull_rows): the per-row FTRL/AdaGrad epilogue (row_epilogue)
        plus ONE scatter."""
        new = row_epilogue(param, state.capacity, rows, gw, gV, pull_vmask)
        return state._replace(
            VVg=_scatter(state.VVg, slots, new, own_cap))

    def apply_grad(state: SGDState, slots: jnp.ndarray,
                   gw: jnp.ndarray, gV: Optional[jnp.ndarray],
                   pull_vmask: Optional[jnp.ndarray]) -> SGDState:
        """kGradient push: FTRL(w) + AdaGrad(V). ``slots`` are sorted unique
        (padding -> TRASH_SLOT, whose gw must be 0). The store's eager
        push: gathers the fused rows itself and delegates the update to
        apply_grad_rows — one definition of the push math."""
        if not has_V:
            w = _gather(state.w, slots)
            sg = _gather(state.sqrt_g, slots)
            z = _gather(state.z, slots)
            w_new, z_new, sg_new = _ftrl(w, z, sg, gw)
            return state._replace(
                w=_scatter(state.w, slots, w_new),
                sqrt_g=_scatter(state.sqrt_g, slots, sg_new),
                z=_scatter(state.z, slots, z_new))
        rows = pull_rows(state, slots)
        return apply_grad_rows(state, slots, rows, gw, gV, pull_vmask)

    # 8-bit rows on one device: evaluate takes the three scalar columns
    # it needs block by block (row_blocks), where the whole table's
    # bitcast of the scalar lanes would make two table-sized
    # temporaries; under a mesh GSPMD partitions the whole-table form
    blocked_cols = quantized(param) and mesh is None

    def _quant_cols(state):
        """(w, live, scale_V) full-table columns of 8-bit rows."""
        off = _layout(state)[3]
        R, nb = row_blocks(state.capacity)

        def cols(i):
            f = scal_f32(jax.lax.dynamic_slice_in_dim(
                state.VVg, i * R, R)[:, off:])
            return f[:, 0], f[:, 4] > 0, f[:, 5]

        return tuple(c.reshape(state.capacity) for c in jax.lax.map(
            cols, jnp.arange(nb, dtype=jnp.int32)))

    @names.leg(names.EVALUATE)
    def evaluate(state: SGDState
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """(penalty, nnz, live_V) over real rows (Evaluate,
        sgd_updater.cc:15-32). Full-table column reads of the fused rows
        — once per epoch. ``live_V`` is the count of live embeddings
        that ``nnz`` charges ``V_dim`` each (0 without an embedding):
        the memory-adaptive model's product, ``nnz``'s own term handed
        out beside it."""
        if blocked_cols:
            w, live, sV = _quant_cols(state)
        else:
            w, _, _, _, live = scal_cols(param, state)
        w = w.at[TRASH_SLOT].set(0.0)
        penalty = jnp.sum(l1 * jnp.abs(w) + 0.5 * l2 * w * w)
        nnz = jnp.sum((w != 0).astype(jnp.float32))
        live_V = jnp.zeros((), jnp.float32)
        if has_V:
            live = live.at[TRASH_SLOT].set(False)
            if blocked_cols:
                # the codes' float32 view is an operand of the sum
                # below, which XLA fuses; no table-sized value is made
                Vcol = fused.dequant_half(state.VVg[:, :param.V_dim], sV,
                                          param.slot_dtype)
            elif quantized(param):
                Vcol = emb_cols_f32(param, state)[0]
            else:
                Vcol = col_V(param, state).astype(jnp.float32)
            Vm = Vcol * live[:, None]
            # quirk preserved: Evaluate charges l2 (not V_l2) on V
            penalty = penalty + jnp.sum(0.5 * l2 * Vm * Vm)
            n_live = jnp.sum(live)
            nnz = nnz + n_live * param.V_dim
            live_V = n_live.astype(jnp.float32)
        return penalty, nnz, live_V

    class _NS:
        pass

    ns = _NS()
    ns.get_rows = get_rows
    ns.apply_count = apply_count
    ns.apply_grad = apply_grad
    ns.evaluate = evaluate
    ns.param = param
    # the table has fused rows: step.py then pulls once and threads the
    # gathered rows to the push
    ns.fused = has_V
    ns.pull_rows = pull_rows
    ns.rows_to_params = rows_to_params
    ns.apply_grad_rows = apply_grad_rows
    return ns
