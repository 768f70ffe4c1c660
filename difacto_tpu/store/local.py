"""Slot store: host feature dictionary + device slot table.

This is the TPU-native "parameter server". The reference's Store
(include/difacto/store.h) routes Push/Pull KV messages to server-side
updaters; here the model lives in device arrays and the host keeps only the
feature-id -> slot mapping:

- ``map_keys(uniq_ids)``: bulk lookup-or-insert of a batch's sorted unique
  (byte-reversed) feature ids -> int32 slot array. This replaces ps-lite's
  key->server-range slicing (kvstore_dist.h:90-118); the "message" is just a
  gather/scatter index vector.
- value-type channels kFeaCount/kWeight/kGradient (include/difacto/store.h:
  33-35) survive as the three jitted entry points apply_count / get_rows(pull)
  / apply_grad(push).
- checkpoint save/load with optional aux state (Updater::Save/Load,
  src/sgd/sgd_updater.h:84-106) and TSV dump (sgd_updater.h:108-139).

Capacity grows by doubling (shape change => one re-jit per doubling,
log2(total/initial) times overall).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..base import FEAID_DTYPE, reverse_bytes
from ..utils import stream
from ..utils import manifest as mft
from ..utils.manifest import CheckpointCorrupt  # noqa: F401 (re-export)
from ..updaters.sgd_updater import (SGDState, SGDUpdaterParam, TRASH_SLOT,
                                    grow_state, init_state, make_fns)

# rows per slab when a table is assembled from host columns
# (SlotStore._assemble_slabs). The slab's f32 intermediates are what a
# load needs on top of the old and the new table: 0.9 GB at 2^18 rows
# of the 512 B flagship row, 3.4 GB at 2^20 (PR 21, on the chip).
_SLAB_ROWS = 1 << 18

# store value-type channel tags (include/difacto/store.h:33-35)
K_FEACOUNT = 1
K_WEIGHT = 2
K_GRADIENT = 3


def fs_shard_path(path: str, shard: int, count: int) -> str:
    """Per-shard checkpoint member name: ``<path>_fs-<i>-of-<n>``. The
    decoration is stripped by manifest.family_prefix (like ``_iter-k`` /
    ``_part-r``), so shard members prune and generation-walk with their
    family; only the undecorated stub is a load entry point."""
    return f"{path}_fs-{shard}-of-{count}"


def pad_slots_oob(slots: np.ndarray, cap: int, capacity: int) -> np.ndarray:
    """int32[cap]: sorted unique ``slots`` followed by ascending
    out-of-bounds padding (capacity, capacity+1, ...)."""
    out = np.arange(capacity, capacity + cap, dtype=np.int64)
    out[:len(slots)] = slots
    return out.astype(np.int32)


def hash_slots(rev_ids: np.ndarray, hash_capacity: int) -> np.ndarray:
    """Byte-REVERSED uint64 ids -> int32 slots: the hashed store's single
    slot-assignment rule (modulo into [1, capacity); row 0 stays
    TRASH_SLOT). One definition shared by map_keys, the producer fast
    paths (learners/sgd.py) and collision_stats, so the diagnostic can
    never quietly diverge from the table."""
    cap = np.uint64(hash_capacity - 1)
    return (np.asarray(rev_ids, FEAID_DTYPE) % cap
            + np.uint64(1)).astype(np.int32)


def collision_stats(ids: np.ndarray, hash_capacity: int) -> dict:
    """Hashed-store collision accounting for a set of distinct feature ids.

    The reference's distributed SGD keys the model by exact 64-bit id
    (unbounded unordered_maps, src/sgd/sgd_updater.h:141-176) so no two
    features ever alias; the multi-host hashed store trades that for a
    fixed capacity (SURVEY §7 hard part (d)). This quantifies the trade:
    ``collided_frac`` is the fraction of distinct ids that share their
    slot with at least one other id (those features' gradients merge
    permanently).
    """
    ids = np.unique(np.asarray(ids, dtype=FEAID_DTYPE))
    slots = hash_slots(reverse_bytes(ids), hash_capacity)
    n = len(ids)
    # O(n) accounting — a bincount over the table would allocate
    # O(hash_capacity) (2 GB at a 2^28-row table) for any id count
    _, occ = np.unique(slots, return_counts=True)
    n_slots = len(occ)
    collided = n - int((occ == 1).sum())
    return {
        "n_ids": n,
        "hash_capacity": hash_capacity,
        "load_factor": round(n / max(hash_capacity - 1, 1), 4),
        "slots_used": n_slots,
        "collided_frac": round(collided / max(n, 1), 4),
    }


@functools.lru_cache(maxsize=8)
def _jitted_init(param_fields: tuple, cap: int, mesh):
    """The jitted ``init_state``; under a mesh its outputs are pinned to
    the fs key-range layout. Keyed on the table's geometry, so stores of
    the same geometry (every reload, every test) share one compiled
    program instead of compiling the PRNG afresh per construction."""
    from ..utils import jaxtrace

    param = SGDUpdaterParam(*param_fields)

    def build():
        # 8-bit rows block by block on one device; GSPMD partitions the
        # whole-table draw by shard
        return init_state(param, cap, blocked=mesh is None)

    shardings = None
    if mesh is not None:
        from ..parallel import sharding_tree, state_sharding
        shardings = sharding_tree(jax.eval_shape(build),
                                  state_sharding(mesh))
    return jaxtrace.jit(build, out_shardings=shardings)


@functools.lru_cache(maxsize=8)
def _slab_put(sharding):
    """The in-place ``T[lo:lo+rows] = slab`` of SlotStore._assemble_slabs,
    its output pinned to the table's layout. One jit per layout, so a
    reload of the same geometry (serving swaps models for hours)
    compiles nothing."""
    from ..utils import jaxtrace
    return jaxtrace.jit(
        lambda T, slab, lo: jax.lax.dynamic_update_slice(T, slab, (lo, 0)),
        donate_argnums=0, out_shardings=sharding)


class SlotStore:
    """Single-controller store over one (possibly sharded) slot table.

    With ``mesh`` set, every state array is placed feature-axis-sharded over
    the mesh's ``fs`` axis (parallel/mesh.py) — the TPU analog of ps-lite's
    key-range server sharding. The learner's jit steps then carry matching
    in/out shardings so the table never leaves its layout.
    """

    def __init__(self, param: SGDUpdaterParam,
                 initial_capacity: Optional[int] = None, mesh=None,
                 read_only: bool = False):
        self.param = param
        self.fns = make_fns(param, mesh)
        self.mesh = mesh
        # read-only stores serve inference (serve/, task=pred): lookups
        # never insert into the dictionary, push/apply paths raise, and
        # load() defaults to a weights-only view that never materializes
        # optimizer state (z/sqrt_g/Vg) on the host
        self.read_only = read_only
        # feature dictionary as parallel sorted arrays (id -> slot); bulk
        # lookup/insert is vectorised via searchsorted + merge — the host-side
        # analog of ps-lite's sorted-key requirement (kvstore_dist.h:95).
        # hash_capacity > 0 replaces the dictionary with stateless modular
        # hashing (deterministic across hosts; SURVEY §7 hashed table).
        self.hashed = param.hash_capacity > 0
        self._keys = np.empty(0, dtype=FEAID_DTYPE)
        self._slots = np.empty(0, dtype=np.int64)
        self._next_slot = TRASH_SLOT + 1
        if initial_capacity is None:
            initial_capacity = param.init_capacity
        cap = param.hash_capacity if self.hashed else initial_capacity
        # host-RAM cold tier (capacity/tier.py): the DEVICE table holds
        # only hash_capacity - cold_tier_rows hot rows; logical slots
        # route through the tier's residency map on every pull/push.
        # Read-only (serving) stores ignore the knob — serving holds the
        # full logical table (serve/model.py forces it to 0 anyway).
        tiered = param.cold_tier_rows > 0 and not read_only
        if tiered:
            if not self.hashed:
                raise ValueError("cold_tier_rows requires the hashed "
                                 "store (hash_capacity > 0): dictionary "
                                 "slots have no fixed logical space to "
                                 "tier over")
            if param.V_dim == 0:
                raise ValueError("cold_tier_rows requires V_dim > 0: the "
                                 "tier moves fused rows, the flat layout "
                                 "has none")
            if mesh is not None:
                raise ValueError("cold_tier_rows is single-device only: "
                                 "tier routing runs on the dispatch "
                                 "thread against an unsharded table (use "
                                 "mesh_fs for sharded capacity, or "
                                 "combine fs with slot_dtype)")
            if param.cold_tier_rows >= cap - 1:
                raise ValueError(
                    f"cold_tier_rows={param.cold_tier_rows} must leave at "
                    f"least 2 hot rows of hash_capacity={cap} (trash row "
                    "+ one working row)")
            cap = cap - param.cold_tier_rows
        if self.fs_count > 1:
            # uneven NamedShardings are a jax error at device_put time —
            # fail at construction with the knob to fix (doubling growth
            # preserves divisibility, so checking the initial capacity
            # covers the dictionary store's whole life)
            from ..parallel import validate_fs_capacity
            validate_fs_capacity(cap, self.fs_count)
        self.state: SGDState = self._init_state(cap)
        self.tier = None
        if tiered:
            from ..capacity.tier import ColdTier
            self.tier = ColdTier(self)

    @property
    def fs_count(self) -> int:
        """Feature-shard degree: how many contiguous key-range shards
        the table's capacity axis splits into (1 = single device)."""
        from ..parallel import fs_size
        return fs_size(self.mesh)

    def _init_state(self, cap: int) -> SGDState:
        """The initial table, out of ONE jitted program. Under a mesh its
        outputs are pinned to the fs key-range layout, so no device ever
        holds more than its own capacity/fs rows — building on the
        default device and resharding after would put the whole table on
        device 0 first, which an fs-times-larger table cannot afford. On
        one device the jit is what keeps the peak at the table itself
        (4.297 GB for the 4.295 GB 2^23-row flagship table; run eagerly,
        init_state's f32 [capacity, k] random V and every intermediate
        stay alive at once and the same table peaked at 12.9 GB — PR 21,
        on the chip). Same values either way: the counter-based PRNG
        does not depend on the partitioning."""
        return _jitted_init(dataclasses.astuple(self.param), cap,
                            self.mesh)()

    def _place(self, state: SGDState) -> SGDState:
        if self.mesh is None:
            return state
        from ..parallel import shard_pytree, state_sharding
        return shard_pytree(state, state_sharding(self.mesh))

    # ------------------------------------------------------------- keys
    @property
    def num_features(self) -> int:
        return len(self._keys)

    @property
    def next_slot(self) -> int:
        """One past the highest assigned slot — deferred-growth callers
        (map_keys(grow=False)) compare this against the device capacity."""
        return self._next_slot

    def map_keys(self, keys: np.ndarray, insert: bool = True,
                 grow: bool = True) -> np.ndarray:
        """Map *unique* uint64 ids -> int32 slots; unknown ids are inserted
        (the reference's operator[] inserts on Get too, sgd_updater.cc:46) or
        mapped to TRASH_SLOT when insert=False. New slots are assigned in the
        input's appearance order.

        ``grow=False`` records the inserted keys but does NOT grow the
        device state — for callers on a lookahead thread (the SPMD control
        plane) that must not swap the table buffers under an in-flight
        step; they call :meth:`grow_to` from the dispatch thread before
        the first step that uses the new slots."""
        if self.read_only:
            # serving lookups must not mutate the dictionary: unknown ids
            # map to TRASH_SLOT (whose row is all-zero, so they contribute
            # nothing to a prediction)
            insert = False
        keys = np.asarray(keys, dtype=FEAID_DTYPE)
        if self.hashed:
            return hash_slots(keys, self.param.hash_capacity)
        n = len(self._keys)
        out = np.full(len(keys), TRASH_SLOT, dtype=np.int32)
        if n:
            idx = np.searchsorted(self._keys, keys)
            safe = np.minimum(idx, n - 1)
            hit = (idx < n) & (self._keys[safe] == keys)
            out[hit] = self._slots[idx[hit]]
        else:
            hit = np.zeros(len(keys), dtype=bool)
        if insert:
            miss = ~hit
            n_new = int(miss.sum())
            if n_new:
                new_keys = keys[miss]
                new_slots = self._next_slot + np.arange(n_new, dtype=np.int64)
                out[miss] = new_slots.astype(np.int32)
                self._next_slot += n_new
                order = np.argsort(new_keys, kind="stable")
                nk, ns = new_keys[order], new_slots[order]
                pos = np.searchsorted(self._keys, nk)
                self._keys = np.insert(self._keys, pos, nk)
                self._slots = np.insert(self._slots, pos, ns)
                if grow:
                    self._ensure_capacity(self._next_slot)
        return out

    def lookup(self, keys: np.ndarray) -> np.ndarray:
        """Slots for known ids, TRASH_SLOT for unknown (no insertion)."""
        return self.map_keys(keys, insert=False)

    def map_keys_dedup(self, keys: np.ndarray,
                       counts: Optional[np.ndarray] = None):
        """map_keys + in-batch collision dedup (hashed mode).

        Returns ``(slots, remap, counts)`` with ``slots`` SORTED unique —
        the device step's scatter/gather kernels declare
        ``indices_are_sorted + unique_indices`` (a measured ~20% step win),
        so this invariant is load-bearing. ``remap`` is None when the raw
        slots already satisfy it; otherwise ``remap[i]`` is the new position
        of input key ``i`` — the caller rewrites its localized COO indices
        through it. In hashed mode distinct ids can also collide into one
        slot within a batch; the same remap merges them, so colliding
        features genuinely alias (their gradients segment-sum into the
        shared row) instead of nondeterministically dropping one update.
        ``counts`` are aggregated the same way.
        """
        slots = self.map_keys(keys)
        n = len(slots)
        if n > 1 and (slots[1:] <= slots[:-1]).any():
            uniq, inv = np.unique(slots, return_inverse=True)
            if counts is not None:
                counts = np.bincount(
                    inv, weights=counts, minlength=len(uniq)
                ).astype(np.float32)
            return uniq.astype(np.int32), inv, counts
        return slots, None, counts

    def capacity_for(self, need: int, current: Optional[int] = None) -> int:
        """The table capacity after growing ``current`` (default: the live
        capacity) to hold ``need`` slots — the single definition of the
        doubling rule, shared with deferred-growth callers (the SPMD
        exchange computes OOB slot padding against the capacity the
        dispatch thread WILL have, so both sites must agree)."""
        cap = self.state.capacity if current is None else current
        while cap < need:
            cap *= 2
        return cap

    def _ensure_capacity(self, need: int) -> None:
        cap = self.capacity_for(need)
        if cap == self.state.capacity:
            return
        self.state = self._place(grow_state(self.param, self.state, cap))

    def grow_to(self, capacity: int) -> None:
        """Grow the device state to exactly ``capacity`` rows (a power-of-two
        multiple of the current capacity, as tracked by a deferred-growth
        caller — see map_keys(grow=False)). No-op when already there."""
        if capacity > self.state.capacity:
            self.state = self._place(grow_state(self.param, self.state,
                                                capacity))

    def pad_slots(self, slots: np.ndarray, cap: int) -> jnp.ndarray:
        """Pad sorted unique slots to ``cap`` with ASCENDING out-of-bounds
        indices — keeps the device kernels' indices_are_sorted +
        unique_indices declarations truthful; OOB lanes gather zeros and
        scatter to nowhere (mode fill/drop)."""
        out = pad_slots_oob(slots, cap, self.state.capacity)
        if self.mesh is not None:
            from ..parallel import put_global, replicated
            return put_global(out, replicated(self.mesh))
        return jnp.asarray(out)

    # ------------------------------------------------------------- KV API
    # Reference-shaped Push/Pull for learners that want the explicit KV
    # contract (L-BFGS/BCD); the SGD hot path fuses these into its jit step.
    def pull(self, keys: np.ndarray) -> Tuple[np.ndarray, Optional[np.ndarray],
                                              Optional[np.ndarray]]:
        # get_rows declares sorted+unique indices, but raw map_keys output is
        # insertion-ordered (dictionary mode) and can repeat (hashed
        # collisions) — dedup to the sorted unique slot set and remap the
        # returned rows back to the caller's key order, mirroring push
        slots_np, remap, _ = self.map_keys_dedup(keys)
        perm = None
        if self.tier is not None:
            # logical slots -> device hot rows (promoting cold rows);
            # gather results come back in routed order, perm maps them
            # to the sorted-slot order the remap step expects
            slots_np, _, perm = self.tier.route(slots_np)
        got = self.fns.get_rows(self.state, jnp.asarray(slots_np))
        w, V, vmask = got.w, got.V, got.v_mask
        w = np.asarray(w)
        V = None if V is None else np.asarray(V)
        vmask = None if vmask is None else np.asarray(vmask)
        if perm is not None:
            w = w[perm]
            V = None if V is None else V[perm]
            vmask = None if vmask is None else vmask[perm]
        if remap is not None:
            w = w[remap]
            V = None if V is None else V[remap]
            vmask = None if vmask is None else vmask[remap]
        return w, V, vmask

    def push(self, keys: np.ndarray, val_type: int,
             gw: np.ndarray, gV: Optional[np.ndarray] = None,
             vmask: Optional[np.ndarray] = None) -> None:
        if self.read_only:
            raise RuntimeError(
                "push on a read-only store: this SlotStore was opened "
                "weights-only for inference (serve/task=pred) and carries "
                "no optimizer state to update")
        slots_np, remap, _ = self.map_keys_dedup(keys)
        if remap is not None:
            # hashed-mode in-batch collisions: sum the colliding values so
            # aliased features accumulate (scatter .set requires unique slots)
            n = len(slots_np)
            gw = np.bincount(remap, weights=np.asarray(gw, np.float64),
                             minlength=n).astype(np.float32)
            if gV is not None:
                agg = np.zeros((n,) + np.asarray(gV).shape[1:],
                               dtype=np.float32)
                np.add.at(agg, remap, np.asarray(gV))
                gV = agg
            if vmask is not None:
                vm = np.zeros(n, dtype=np.float32)
                np.maximum.at(vm, remap, np.asarray(vmask, np.float32))
                vmask = vm
        if self.tier is not None:
            # route to device rows and carry the per-slot values along
            # (order[j] = slot position now at routed position j); a
            # degraded slot (promote fault) lands on an OOB lane whose
            # scatter is dropped — that update is lost, the row is not
            slots_np, order, _ = self.tier.route(slots_np)
            gw = np.asarray(gw)[order]
            if gV is not None:
                gV = np.asarray(gV)[order]
            if vmask is not None:
                vmask = np.asarray(vmask)[order]
        slots = jnp.asarray(slots_np)
        if val_type == K_FEACOUNT:
            self.state = self.fns.apply_count(self.state, slots,
                                              jnp.asarray(gw))
        elif val_type == K_GRADIENT:
            self.state = self.fns.apply_grad(
                self.state, slots, jnp.asarray(gw),
                None if gV is None else jnp.asarray(gV),
                None if vmask is None else jnp.asarray(vmask))
        else:
            raise ValueError(f"unknown val_type {val_type}")

    def evaluate(self) -> Tuple[float, float]:
        return self.evaluate_all()[:2]

    def evaluate_all(self) -> Tuple[float, float, float]:
        """(penalty, nnz, live_V) on the host."""
        from ..utils import jaxtrace
        vals = jaxtrace.fetch(jnp.stack(self.evaluate_dev()),
                              point="store.evaluate")
        return tuple(float(v) for v in vals)

    def evaluate_dev(self):
        """(penalty, nnz, live_V) as DEVICE scalars — callers batch the
        fetch with other pending metrics (a sync fetch drains the
        dispatch queue)."""
        if not hasattr(self, "_eval_jit"):
            from ..utils import jaxtrace
            self._eval_jit = jaxtrace.jit(self.fns.evaluate)
        return self._eval_jit(self.state)

    # ------------------------------------------------------------- ckpt
    def _sorted_items(self) -> Tuple[np.ndarray, np.ndarray]:
        return self._keys, self._slots

    def _state_np(self, state: SGDState,
                  keys: Optional[Tuple[str, ...]] = None) -> dict:
        """Host view with the logical V/Vg split (state stores fused VVg,
        halves padded to v_half lanes; the split slices back to the
        logical V_dim columns so checkpoints/dumps are pad-free and
        layout-independent). Multi-host: the table is fs-sharded within
        each host (dp replicates across hosts), so every piece is locally
        addressable."""
        from ..parallel.multihost import to_local_numpy
        from ..updaters.sgd_updater import (col_V, col_Vg, emb_cols_f32,
                                            quantized, scal_cols)
        # build and fetch ONLY what the caller writes: the device->host
        # copy is the cost (a full 4.2M-row V16 state is ~600 MB), a
        # non-aux save/dump never touches z/sqrt_g/Vg,
        # and the V/Vg slices materialize full [capacity, k] copies in
        # HBM if dispatched (the scal unpack is one pass serving all
        # five scalar columns, so it always runs)
        w, zz, sg, cnt, live = scal_cols(self.param, state)
        cols = {"w": w, "z": zz, "sqrt_g": sg, "cnt": cnt, "v_live": live}
        if quantized(self.param):
            # 8-bit rows hold codes, not values: the host view must
            # dequantize through the per-row scale lanes so checkpoints
            # and dumps stay layout-independent logical f32
            if keys is None or "V" in keys or "Vg" in keys:
                Vf, Vgf = emb_cols_f32(self.param, state)
                cols["V"], cols["Vg"] = Vf, Vgf
        else:
            if keys is None or "V" in keys:
                cols["V"] = col_V(self.param, state)
            if keys is None or "Vg" in keys:
                cols["Vg"] = col_Vg(self.param, state)
        if keys is not None:
            cols = {f: cols[f] for f in keys}
        d = {f: to_local_numpy(a) for f, a in cols.items()}
        # bf16 storage (V_dtype) becomes float32 on the host: numpy/npz
        # have no bfloat16
        for f in ("V", "Vg"):
            if f in d:
                d[f] = d[f].astype(np.float32)
        return d

    def _logical_np(self, keys: Optional[Tuple[str, ...]] = None) -> dict:
        """_state_np over the LOGICAL slot space: identical to the device
        view for untiered stores; with a cold tier the [device_rows]
        columns expand to the full [hash_capacity] rows (hot rows at
        their owning slot, demoted rows decoded from their host bytes,
        virgin tail rows with their deterministic V init) — the dense
        view every checkpoint/dump writes, so artifacts never depend on
        the tier's residency at save time."""
        st = self._state_np(self.state, keys=keys)
        if self.tier is not None:
            st = self.tier.logical_cols(st)
        return st

    def maybe_evict(self) -> int:
        """Occupancy-pressure eviction (``evict_occupancy`` knob): when
        the occupied fraction of device rows exceeds the threshold,
        demote the lowest-count occupied rows until occupancy drops to
        0.9x the threshold. With the cold tier on, evicted rows move to
        host RAM and stay fully addressable (a pure capacity lever);
        without it their FTRL/AdaGrad scalars reset to virgin (the V
        codes and quant scales survive, masked by live=False). COLD
        path — epoch boundaries (learners/sgd.py), never the dispatch
        loop. Returns rows evicted; counted into
        ``store_evictions_total``."""
        thr = self.param.evict_occupancy
        if thr <= 0:
            return 0
        st = self._state_np(self.state, keys=("w", "cnt", "v_live"))
        occupied = (st["w"] != 0) | (st["cnt"] != 0)
        if self.param.V_dim > 0:
            occupied |= np.asarray(st["v_live"], bool)
        occupied[TRASH_SLOT] = False
        cap = self.state.capacity
        n_occ = int(occupied.sum())
        if n_occ / max(cap - 1, 1) <= thr:
            return 0
        target = int(0.9 * thr * (cap - 1))
        n_evict = n_occ - target
        rows = np.nonzero(occupied)[0]
        order = np.argsort(st["cnt"][rows], kind="stable")
        victims = np.sort(rows[order[:n_evict]])
        if self.tier is not None:
            n = self.tier.demote_rows(victims)
        else:
            n = self._reset_rows(victims)
        if n:
            from ..obs import REGISTRY
            REGISTRY.counter(
                "store_evictions_total",
                "table rows evicted under occupancy pressure "
                "(evict_occupancy)").inc(n)
        return n

    def _reset_rows(self, victims: np.ndarray) -> int:
        """Reset the FTRL/AdaGrad scalars of the given sorted device
        rows to virgin (w=z=sqrt_g=cnt=0, live=False) — the no-tier
        eviction: the rows stay allocated (the hashed table is dense)
        but stop contributing to predictions and restart their FTRL
        trajectory on next touch. Embedding codes and quant scales are
        left in place; live=False masks them."""
        n = len(victims)
        if n == 0:
            return 0
        from ..updaters.sgd_updater import pack_scal, row_layout, scal_f32
        from ..ops import fused
        if self.param.V_dim == 0:
            vj = jnp.asarray(victims)
            st = self.state
            self.state = self._place(st._replace(
                w=st.w.at[vj].set(0.0), z=st.z.at[vj].set(0.0),
                sqrt_g=st.sqrt_g.at[vj].set(0.0),
                cnt=st.cnt.at[vj].set(0.0),
                v_live=st.v_live.at[vj].set(False)))
            return n
        _, _, _, off = row_layout(self.param, self.state.capacity)
        from ..ops.batch import bucket
        pad = pad_slots_oob(victims.astype(np.int32), bucket(n),
                            self.state.capacity)
        sl = jnp.asarray(pad)
        rows = fused.gather_rows(self.state.VVg, sl)
        f = scal_f32(rows[:, off:])
        zero = jnp.zeros(rows.shape[0], jnp.float32)
        scal = pack_scal(zero, zero, zero, zero,
                         jnp.zeros(rows.shape[0], bool), rows.dtype,
                         scale_V=f[:, 5], scale_Vg=f[:, 6])
        out = jnp.concatenate([rows[:, :off], scal], axis=1)
        self.state = self._place(self.state._replace(
            VVg=fused.scatter_rows(self.state.VVg, sl, out)))
        return n

    # --------------------------------------------------- WAL row surgery
    def wal_geometry(self) -> dict:
        """The geometry stamp every WAL segment carries and replay
        validates before applying (durability/wal.py): a delta logged
        against a different capacity / layout / quantization must stop
        replay typed, never scatter into the wrong rows."""
        return {"hash_capacity": int(self.param.hash_capacity),
                "capacity": int(self.state.capacity),
                "V_dim": int(self.param.V_dim),
                "slot_dtype": self.param.slot_dtype,
                "row_width": int(self.state.VVg.shape[1])}

    def wal_touched_rows(self, slots: np.ndarray) -> dict:
        """Host copies of the given device rows EXACTLY as the table
        stores them — fused VVg CONTAINER rows for V_dim > 0 (so a
        quantized ``slot_dtype`` table logs container bytes and replay
        is bit-exact with no dequantize round-trip), or the five flat
        columns of the V_dim = 0 layout. The WAL's append-side read;
        one small host gather per flush window, off the jit step."""
        slots = np.asarray(slots, dtype=np.int32)
        n = len(slots)
        if n == 0:
            return {}
        if self.param.V_dim == 0:
            sl = jnp.asarray(slots)
            st = self.state
            return {k: np.asarray(getattr(st, k)[sl])
                    for k in ("w", "z", "sqrt_g", "cnt", "v_live")}
        from ..ops import fused
        from ..ops.batch import bucket
        pad = pad_slots_oob(slots, bucket(n), self.state.capacity)
        rows = fused.gather_rows(self.state.VVg, jnp.asarray(pad))
        return {"VVg": np.asarray(rows[:n])}

    def apply_wal_rows(self, slots: np.ndarray, arrays: dict) -> int:
        """Scatter replayed WAL rows back into the table — the inverse
        of :meth:`wal_touched_rows`, byte-exact by construction (the
        logged container/column bytes land unchanged). Replay-path only
        (durability/recover.py), never concurrent with dispatch."""
        slots = np.asarray(slots, dtype=np.int32)
        n = len(slots)
        if n == 0:
            return 0
        st = self.state
        if self.param.V_dim == 0:
            cols = ("w", "z", "sqrt_g", "cnt", "v_live")
            for k in cols:
                if len(arrays[k]) != n:
                    raise ValueError(
                        f"WAL column {k!r} has {len(arrays[k])} rows "
                        f"for {n} slots")
            sl = jnp.asarray(slots)
            self.state = self._place(st._replace(**{
                k: getattr(st, k).at[sl].set(
                    jnp.asarray(np.asarray(arrays[k]).astype(
                        getattr(st, k).dtype)))
                for k in cols}))
            return n
        from ..ops import fused
        from ..ops.batch import bucket
        width = st.VVg.shape[1]
        rows = np.asarray(arrays["VVg"]).reshape(n, width)
        if rows.dtype != st.VVg.dtype:
            raise ValueError(
                f"WAL rows are {rows.dtype} but the table stores "
                f"{st.VVg.dtype}: geometry mismatch")
        pad = pad_slots_oob(slots, bucket(n), st.capacity)
        full = np.zeros((len(pad), width), dtype=rows.dtype)
        full[:n] = rows
        self.state = self._place(st._replace(
            VVg=fused.scatter_rows(st.VVg, jnp.asarray(pad),
                                   jnp.asarray(full))))
        return n

    def capacity_stats(self) -> dict:
        """Effective-capacity accounting of the three levers:
        logical addressable rows vs what an fp32/no-tier table of the
        SAME per-device byte budget would hold."""
        import dataclasses
        from ..updaters.sgd_updater import state_bytes
        dev_rows = self.state.capacity
        logical = self.param.hash_capacity if self.hashed else dev_rows
        fs = self.fs_count
        bytes_total = state_bytes(self.param, dev_rows)
        base = dataclasses.replace(self.param, slot_dtype="fp32",
                                   V_dtype="float32", cold_tier_rows=0)
        base_bpr = state_bytes(base, dev_rows) / max(dev_rows, 1)
        baseline_rows = bytes_total / max(base_bpr, 1e-9)
        out = {
            "slot_dtype": self.param.slot_dtype,
            "logical_rows": logical,
            "device_rows": dev_rows,
            "table_bytes_per_device": bytes_total // fs,
            "effective_rows_per_device": logical // fs,
            "capacity_multiplier": round(logical / max(baseline_rows,
                                                       1e-9), 3),
        }
        if self.tier is not None:
            out["tier"] = self.tier.stats()
        return out

    def _assemble_state(self, arr: dict, capacity: int) -> SGDState:
        """Inverse of _state_np: dict with logical-width V/Vg -> SGDState
        with the (possibly lane-padded) fused VVg. ``capacity`` is the
        LIVE table capacity the state is being assembled for — the
        pad_v_rows layout decision must match the table that will train,
        not the artifact's row count (a partial/sharded save with fewer
        rows would otherwise silently re-enable padding on a table that
        runs unpadded for memory reasons, round-4 advisor finding)."""
        V = np.asarray(arr.pop("V"), dtype=np.float32)
        Vg = np.asarray(arr.pop("Vg"), dtype=np.float32)
        if V.shape[0] != capacity:
            raise ValueError(
                f"checkpoint arrays have {V.shape[0]} rows but the table "
                f"capacity is {capacity}: partial-state loads are not "
                "supported (the v_half layout decision would diverge)")
        if self.param.V_dim == 0:
            return SGDState(VVg=jnp.zeros((capacity, 0), jnp.float32),
                            **{f: jnp.asarray(a) for f, a in arr.items()})
        cols = (V, Vg, arr["w"], arr["z"], arr["sqrt_g"], arr["cnt"],
                arr["v_live"])
        T = self._assemble_slabs(cols, capacity)
        empty = jnp.zeros(0, jnp.float32)
        return SGDState(w=empty, z=empty + 0, sqrt_g=empty + 0,
                        cnt=empty + 0, VVg=T,
                        v_live=jnp.zeros(0, dtype=bool))

    def _assemble_slabs(self, cols: tuple, capacity: int) -> jnp.ndarray:
        """The table is assembled a slab of rows at a time into a
        destination that is born in its final (fs-sharded) layout and
        updated in place. build_rows over a whole big table keeps several
        full-table f32 intermediates alive on ONE device — their 64-lane
        halves tile-pad to 128, so a 2^23-row V64 table (4 GiB) asked
        for 8 GiB more and could be loaded nowhere (PR 21, on the chip),
        and under a mesh the whole table passed through device 0. Rows
        are independent, so any slab size gives the same bits; a table
        of up to _SLAB_ROWS rows is one slab."""
        from ..updaters.sgd_updater import build_rows, row_layout, v_dtype
        _, _, Wx, _ = row_layout(self.param, capacity)
        dt = v_dtype(self.param)
        sharding = None
        if self.mesh is not None:
            from ..parallel import state_sharding
            sharding = state_sharding(self.mesh)(
                jax.ShapeDtypeStruct((capacity, Wx), dt))
        put = _slab_put(sharding)
        T = jnp.zeros((capacity, Wx), dt, device=sharding)
        for lo in range(0, capacity, _SLAB_ROWS):
            hi = min(lo + _SLAB_ROWS, capacity)
            T = put(T, build_rows(self.param, capacity,
                                  *(c[lo:hi] for c in cols)), lo)
        return T

    def save(self, path: str, save_aux: bool = False,
             epoch: Optional[int] = None, keep: int = 0,
             shards: Optional[int] = None) -> int:
        """Checkpoint non-empty entries, sorted by key. Hashed mode has no
        id dictionary — the full dense table is saved instead.

        Every save leaves a ``<path>.manifest.json`` sidecar (per-array
        sha256, row count, learner, epoch, monotonically increasing
        generation; utils/manifest.py) written AFTER the npz finalizes —
        the commit marker a torn write can't fake. ``keep > 0`` retires
        interval (``_iter-k``) checkpoints of this family older than the
        newest ``keep`` epochs; the final undecorated model is never
        pruned.

        ``shards`` (default: the mesh's fs degree) splits a HASHED
        table's dense arrays into per-key-range member files
        ``<path>_fs-<i>-of-<n>`` — one per fs shard, each with its own
        verifying manifest — plus an array-free stub at ``<path>``
        written LAST as the generation's commit marker. An fs-sharded
        table bigger than one device's HBM round-trips through these
        without the artifact ever pretending to be a one-device array,
        and a corrupt shard fails typed so loaders walk back a
        generation (load below, serve/model.py)."""
        saved = ("w", "cnt", "v_live", "V") + (
            ("z", "sqrt_g", "Vg") if save_aux else ())
        if shards is None:
            shards = self.fs_count if self.hashed else 1
        if self.hashed and shards > 1:
            return self._save_sharded(path, saved, save_aux, epoch, keep,
                                      shards)
        if self.hashed:
            # logical view: a tiered store saves the full
            # [hash_capacity]-row table (hot + host-RAM rows), so the
            # artifact is residency-independent. slot_dtype /
            # cold_tier_rows stamps travel for loaders (serve/model.py
            # adopts the quantization, never the tier — serving holds
            # the whole table); arrays are ALWAYS logical f32
            st = self._logical_np(keys=saved)
            arrays = dict(hash_capacity=np.array(self.param.hash_capacity),
                          V_dim=np.array(self.param.V_dim),
                          save_aux=np.array(save_aux),
                          learner=np.array("sgd"),
                          slot_dtype=np.array(self.param.slot_dtype),
                          cold_tier_rows=np.array(
                              self.param.cold_tier_rows),
                          **{k: st[k] for k in saved})
            n = int((st["w"] != 0).sum())
        else:
            keys, slots = self._sorted_items()
            st = self._state_np(self.state, keys=saved)
            live = (st["w"][slots] != 0) | (st["cnt"][slots] != 0)
            if self.param.V_dim > 0:
                live |= st["v_live"][slots]
            keys, slots = keys[live], slots[live]
            arrays = dict(
                keys=keys,
                w=st["w"][slots],
                cnt=st["cnt"][slots],
                v_live=st["v_live"][slots],
                V=st["V"][slots],
                save_aux=np.array(save_aux),
                V_dim=np.array(self.param.V_dim),
                learner=np.array("sgd"),
                slot_dtype=np.array(self.param.slot_dtype),
            )
            if save_aux:
                arrays.update(z=st["z"][slots], sqrt_g=st["sqrt_g"][slots],
                              Vg=st["Vg"][slots])
            n = len(keys)
        man = {"learner": "sgd", "rows": n, "save_aux": bool(save_aux),
               "generation": mft.next_generation(path)}
        if epoch is not None:
            man["epoch"] = int(epoch)
        # uncompressed: a trained 4.2M-row V16 state is ~300 MB and
        # np.savez_compressed writes it at ~6 MB/s — ~50 s added to
        # every epoch checkpoint (the rec data cache dropped zlib
        # for the same reason)
        stream.save_npz(path, compress=False, manifest=man,
                        fault_point="ckpt.write", **arrays)
        if keep > 0:
            import re
            m = re.search(r"_part-(\d+)", path)
            mft.prune_checkpoints(path, keep,
                                  rank=int(m.group(1)) if m else None)
        return n

    def _save_sharded(self, path: str, saved, save_aux: bool,
                      epoch: Optional[int], keep: int, shards: int) -> int:
        """Per-key-range checkpoint of the hashed table (see save):
        shard files carry rows [lo, hi) of every column plus their own
        geometry stamp; the stub closes the generation."""
        from ..parallel import fs_shard_bounds
        cap = self.param.hash_capacity
        bounds = fs_shard_bounds(cap, shards)
        st = self._logical_np(keys=saved)
        gen = mft.next_generation(path)
        n = int((st["w"] != 0).sum())
        geom = dict(hash_capacity=np.array(cap),
                    V_dim=np.array(self.param.V_dim),
                    save_aux=np.array(save_aux),
                    learner=np.array("sgd"),
                    slot_dtype=np.array(self.param.slot_dtype),
                    cold_tier_rows=np.array(self.param.cold_tier_rows),
                    fs_count=np.array(shards))
        for i, (lo, hi) in enumerate(bounds):
            man = {"learner": "sgd",
                   "rows": int((st["w"][lo:hi] != 0).sum()),
                   "save_aux": bool(save_aux), "generation": gen,
                   "fs_shard": i, "fs_count": shards}
            if epoch is not None:
                man["epoch"] = int(epoch)
            stream.save_npz(
                fs_shard_path(path, i, shards), compress=False,
                manifest=man, fault_point="ckpt.write",
                row_lo=np.array(lo), row_hi=np.array(hi), **geom,
                **{k: st[k][lo:hi] for k in saved})
        # array-free stub LAST: its manifest is the generation's commit
        # marker — a save torn between shard files leaves no stub
        # manifest, so the generation reads as incomplete, never as a
        # half-written table
        man = {"learner": "sgd", "rows": n, "save_aux": bool(save_aux),
               "generation": gen, "fs_count": shards}
        if epoch is not None:
            man["epoch"] = int(epoch)
        stream.save_npz(path, compress=False, manifest=man,
                        fault_point="ckpt.write", **geom)
        if keep > 0:
            import re
            m = re.search(r"_part-(\d+)", path)
            mft.prune_checkpoints(path, keep,
                                  rank=int(m.group(1)) if m else None)
        return n

    def load(self, path: str, weights_only: Optional[bool] = None,
             verify: bool = True, require_manifest: bool = False) -> int:
        """Restore a checkpoint. ``weights_only`` (default: the store's
        read_only flag) loads just what inference reads — w / cnt /
        v_live / V — and never materializes optimizer state (z, sqrt_g,
        Vg) on the host even when the checkpoint carries it: aux columns
        are stride-0 zero views, so a serving process pays no host RAM
        for state it will never update.

        ``verify`` (default on) raises a typed
        :class:`CheckpointCorrupt` on truncation / digest mismatch
        instead of crashing in numpy — in ONE IO pass: members hash as
        they decompress for the load and the few the load skips are
        swept before any state commits (utils/manifest.py VerifiedNpz —
        the old separate verify pass read every byte twice).
        ``verify=False`` skips digesting for callers that already
        verified the exact file. ``require_manifest`` additionally
        treats a missing sidecar as corruption — the contract for files
        this codebase wrote (auto_resume candidates always have one)."""
        if weights_only is None:
            weights_only = self.read_only
        loaded = (("w", "cnt", "v_live", "V") if weights_only
                  else ("w", "cnt", "v_live", "V", "z", "sqrt_g", "Vg"))

        def _aux(shape):
            # stride-0 zeros: a weights-only load allocates no aux memory
            return np.broadcast_to(np.float32(0.0), shape)

        ctx = (mft.open_verified(path, require_manifest=require_manifest,
                                 fault_point="ckpt.read") if verify
               else stream.load_npz(path, fault_point="ckpt.read"))
        # digest sweep of manifest members the load never touched; runs
        # BEFORE state commits so a corrupt file can't leave a half-
        # loaded store behind (plain npz ctx: nothing to sweep)
        fin = getattr(ctx, "finish", lambda: None)
        with ctx as z:
            if self.hashed != ("hash_capacity" in z.files):
                raise ValueError(
                    "checkpoint store mode mismatch: "
                    f"checkpoint is {'hashed' if not self.hashed else 'a dictionary model'}, "
                    f"store is {'hashed' if self.hashed else 'dictionary-based'}")
            if "hash_capacity" in z.files:
                if int(z["hash_capacity"]) != self.param.hash_capacity:
                    raise ValueError("hashed checkpoint needs a store with "
                                     "the same hash_capacity")
                ck_vdim = int(z["V_dim"]) if "V_dim" in z.files else 0
                if ck_vdim != self.param.V_dim:
                    raise ValueError(
                        f"checkpoint V_dim={ck_vdim} != configured "
                        f"V_dim={self.param.V_dim} ({path})")
                if "fs_count" in z.files and "w" not in z.files:
                    # per-key-range stub (save shards > 1): the table
                    # lives in <path>_fs-<i>-of-<n> members — sweep the
                    # stub's digests, then assemble from the shards
                    fin()
                    return self._load_sharded(
                        path, int(z["fs_count"]), loaded, weights_only,
                        verify)
                # host-side zeros template — no device round trip: every
                # key the checkpoint carries overwrites it in full, and
                # the aux keys a non-aux checkpoint omits (z, sqrt_g, Vg)
                # are zero at init anyway. (The dictionary load below
                # keeps the device init_state template: its rows beyond
                # the checkpoint retain their random V init.)
                cap, k_dim = self.param.hash_capacity, self.param.V_dim
                az = _aux if weights_only else \
                    (lambda s: np.zeros(s, np.float32))
                arr = {"w": np.zeros(cap, np.float32),
                       "z": az(cap),
                       "sqrt_g": az(cap),
                       "cnt": np.zeros(cap, np.float32),
                       "v_live": np.zeros(cap, bool),
                       "V": np.zeros((cap, k_dim), np.float32),
                       "Vg": az((cap, k_dim))}
                for k in loaded:
                    if k in z.files:
                        arr[k] = z[k]
                nnz = int((np.asarray(arr["w"]) != 0).sum())
                fin()
                self._commit_hashed(arr)
                return nnz
            ck_vdim = int(z["V_dim"]) if "V_dim" in z.files else 0
            if ck_vdim != self.param.V_dim:
                raise ValueError(
                    f"checkpoint V_dim={ck_vdim} != configured "
                    f"V_dim={self.param.V_dim} ({path})")
            keys = np.asarray(z["keys"], dtype=FEAID_DTYPE)  # saved sorted
            n = len(keys)
            cap = self.state.capacity
            while cap < n + 1:
                cap *= 2
            st = self._init_state(cap)
            if weights_only:
                arr = {f: a.copy() for f, a in self._state_np(
                    st, keys=("w", "cnt", "v_live", "V")).items()}
                arr["z"] = _aux((cap,))
                arr["sqrt_g"] = _aux((cap,))
                arr["Vg"] = _aux(arr["V"].shape)
            else:
                arr = {f: a.copy() for f, a in self._state_np(st).items()}
            sl = np.arange(1, n + 1)
            arr["w"][sl] = z["w"]
            arr["cnt"][sl] = z["cnt"]
            arr["v_live"][sl] = z["v_live"]
            if z["V"].size:
                arr["V"][sl] = z["V"]
            if not weights_only and "z" in z.files:
                arr["z"][sl] = z["z"]
                arr["sqrt_g"][sl] = z["sqrt_g"]
                if z["Vg"].size:
                    arr["Vg"][sl] = z["Vg"]
            fin()
            # commit only after the digest sweep: the host dictionary and
            # device state move together or not at all
            self.state = self._place(self._assemble_state(arr, cap))
            self._keys = keys
            self._slots = np.arange(1, n + 1, dtype=np.int64)
            self._next_slot = n + 1
        return n

    def _commit_hashed(self, arr: dict) -> None:
        """Commit loaded LOGICAL hashed-table columns [hash_capacity
        rows]: untiered stores assemble the full table on device; a
        tiered store splits at its device capacity — the hot prefix
        becomes device state (residency resets to the identity prefix)
        and the tail re-seeds the host tier (capacity/tier.load_cold).
        Checkpoints therefore round-trip across tier configurations:
        tiered saves load into untiered stores and vice versa."""
        if self.tier is None:
            self.state = self._place(self._assemble_state(
                arr, self.param.hash_capacity))
            return
        dev_cap = self.tier.D
        dev = {k: np.asarray(a)[:dev_cap] for k, a in arr.items()}
        self.state = self._place(self._assemble_state(dev, dev_cap))
        self.tier.load_cold(arr)

    def _load_sharded(self, path: str, fs_count: int, loaded,
                      weights_only: bool, verify: bool) -> int:
        """Assemble the hashed table from its per-key-range shard files
        (save shards > 1). Every shard is digest-verified BEFORE any
        state commits; a missing or mismatched member raises the typed
        :class:`CheckpointCorrupt` so loaders (auto_resume, task=serve)
        walk back to the previous verified generation instead of
        serving a half-assembled table. The assembled host columns are
        placed back through ``_place`` — per-shard slices land straight
        on their owning devices (parallel/mesh.py put_global), so the
        round trip never builds a one-device global array."""
        cap, k_dim = self.param.hash_capacity, self.param.V_dim
        from ..parallel import fs_shard_bounds
        try:
            bounds = fs_shard_bounds(cap, fs_count)
        except ValueError as e:
            raise CheckpointCorrupt(path, str(e)) from e

        def _aux(shape):
            return np.broadcast_to(np.float32(0.0), shape)

        az = _aux if weights_only else (lambda s: np.zeros(s, np.float32))
        arr = {"w": np.zeros(cap, np.float32),
               "z": az(cap),
               "sqrt_g": az(cap),
               "cnt": np.zeros(cap, np.float32),
               "v_live": np.zeros(cap, bool),
               "V": np.zeros((cap, k_dim), np.float32),
               "Vg": az((cap, k_dim))}
        for i, (lo, hi) in enumerate(bounds):
            sp = fs_shard_path(path, i, fs_count)
            try:
                # shard members are always this codebase's writes: the
                # stub declared fs_count, so a manifest-less shard is a
                # torn save, not a legacy file
                sctx = (mft.open_verified(sp, require_manifest=True,
                                          fault_point="ckpt.read")
                        if verify
                        else stream.load_npz(sp, fault_point="ckpt.read"))
            except FileNotFoundError as e:
                raise CheckpointCorrupt(
                    path, f"shard member {sp!r} is missing (torn or "
                          f"partially pruned {fs_count}-shard save)") \
                    from e
            sfin = getattr(sctx, "finish", lambda: None)
            with sctx as sz:
                if (int(sz["hash_capacity"]) != cap
                        or int(sz["fs_count"]) != fs_count
                        or int(sz["row_lo"]) != lo
                        or int(sz["row_hi"]) != hi):
                    raise CheckpointCorrupt(
                        sp, f"shard geometry disagrees with its stub "
                            f"(expected rows [{lo}, {hi}) of {cap} over "
                            f"{fs_count} shards)")
                for k in loaded:
                    if k in sz.files:
                        a = sz[k]
                        if np.asarray(a).shape[0] != hi - lo:
                            raise CheckpointCorrupt(
                                sp, f"array {k!r} has "
                                    f"{np.asarray(a).shape[0]} rows, "
                                    f"shard owns {hi - lo}")
                        arr[k][lo:hi] = a
                sfin()
        nnz = int((arr["w"] != 0).sum())
        self._commit_hashed(arr)
        return nnz

    def shard_stats(self) -> list:
        """Per-key-range shard occupancy: [{shard, row_lo, row_hi, rows,
        occupancy, table_bytes}] — ``rows`` counts non-zero-w slots in
        the shard's range, ``table_bytes`` is the per-device HBM the
        shard pins (updaters.state_bytes / fs). COLD path: reads the
        full w column to the host — epoch boundaries, bench legs and
        stats endpoints, never the dispatch loop."""
        from ..updaters.sgd_updater import state_bytes
        from ..parallel import fs_shard_bounds
        st = self._state_np(self.state, keys=("w",))
        fs = self.fs_count
        bounds = fs_shard_bounds(self.state.capacity, fs)
        per_dev = state_bytes(self.param, self.state.capacity) // fs
        out = []
        for i, (lo, hi) in enumerate(bounds):
            rows = int((st["w"][lo:hi] != 0).sum())
            out.append({"shard": i, "row_lo": lo, "row_hi": hi,
                        "rows": rows,
                        "occupancy": round(rows / max(hi - lo, 1), 6),
                        "table_bytes": per_dev})
        return out

    def publish_shard_stats(self) -> list:
        """shard_stats() pushed into the global metric registry
        (``store_shard_rows`` / ``store_shard_occupancy`` gauges,
        docs/observability.md) — called from cold paths only (see
        shard_stats)."""
        from ..obs import gauge
        stats = self.shard_stats()
        rows_g = gauge("store_shard_rows",
                       "non-empty slot-table rows per fs key-range shard")
        occ_g = gauge("store_shard_occupancy",
                      "filled fraction of each fs key-range shard")
        for s in stats:
            rows_g.labels(shard=str(s["shard"])).set(s["rows"])
            occ_g.labels(shard=str(s["shard"])).set(s["occupancy"])
        return stats

    def dump(self, path: str, dump_aux: bool = False,
             need_reverse: bool = True) -> int:
        """Human-readable TSV export (Updater::Dump, sgd_updater.h:108-139):
        ``feaid size w [sqrt_g z] V... [Vg...]`` per line, skipping empty
        entries. need_reverse un-reverses ids back to the original space.
        Hashed mode has no id dictionary: the first column is the slot id
        and need_reverse is ignored."""
        st = self._logical_np(keys=("w", "v_live", "V") + (
            ("sqrt_g", "z", "Vg") if dump_aux else ()))
        if self.hashed:
            keep = st["w"] != 0
            if self.param.V_dim > 0:  # keep l1-shrunk rows with live V
                keep |= st["v_live"]
            keep[TRASH_SLOT] = False
            slots = np.nonzero(keep)[0]
            keys = slots.astype(FEAID_DTYPE)
            need_reverse = False
        else:
            keys, slots = self._sorted_items()
        n = 0
        with stream.open_stream(path, "w") as f:
            for k, s in zip(keys, slots):
                w = st["w"][s]
                live = bool(st["v_live"][s]) and self.param.V_dim > 0
                if w == 0 and not live:
                    continue
                key = reverse_bytes(int(k)) if need_reverse else int(k)
                size = 1 + (self.param.V_dim if live else 0)
                cols = [str(key), str(size), repr(float(w))]
                if dump_aux:
                    cols += [repr(float(st["sqrt_g"][s])),
                             repr(float(st["z"][s]))]
                if live:
                    cols += [repr(float(v)) for v in st["V"][s]]
                    if dump_aux:
                        cols += [repr(float(v)) for v in st["Vg"][s]]
                f.write("\t".join(cols) + "\n")
                n += 1
        return n
