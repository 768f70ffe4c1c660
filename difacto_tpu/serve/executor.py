"""Bucketed read-only predict executor — the serving device path.

One small set of pre-jitted predict programs serves every request batch:
rows / nnz / distinct-feature counts are padded up to STICKY bucket caps
(data/pack_stream.ShapeSchedule over ops/batch.py bucket rungs) — each
dim pads to the largest bucket seen so far, so micro-batch occupancy
jitter collapses onto one compiled program per traffic regime instead of
compiling every (rows, nnz, uniq) bucket combination the arrival process
happens to produce. Caps only grow (log-many compiles over a server's
life, each at a shape's first occurrence); after warmup every dispatch
is a bucket HIT — the ISSUE 2 acceptance gate — and ``stats`` proves it.

The same executor backs ``task=pred`` (learners/sgd.py routes its batch
path here) and ``task=serve`` (serve/server.py): identical localization,
identical packing (ops/batch.py pack_batch), identical jitted program
(step.py make_predict_fn) — which is what makes offline prediction files
and online responses bit-identical for the same rows.

The executor never mutates the store: dictionary lookups use
``insert=False`` (unknown feature ids resolve to the all-zero TRASH row
and contribute nothing), so it composes with the read-only weights-only
stores serving loads (store/local.py) as well as a learner's live store.
"""

from __future__ import annotations

import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..data.localizer import compact
from ..data.pack_stream import ShapeSchedule
from ..data.rowblock import RowBlock
from ..losses import LossSpec, create as create_loss
from ..ops.batch import pack_batch, unpack_batch
from ..step import make_predict_fn
from ..store.local import SlotStore, pad_slots_oob
from ..utils import jaxtrace
from ..utils.locktrace import mutex


def sigmoid(pred: np.ndarray) -> np.ndarray:
    """Raw margin -> probability, shared by _save_pred-style writers and
    the serve response formatter (one definition, identical bytes)."""
    return 1.0 / (1.0 + np.exp(-np.asarray(pred)))


class PredictExecutor:
    """Shape-bucketed batch scoring over a SlotStore.

    ``predict(blk)`` -> (scores[:rows] np.float32 raw margins, objv, auc)
    with objv/auc left as device scalars so callers batch the fetch.
    Dispatch is single-threaded by contract (the micro-batcher owns it in
    serving; the pred loop in batch mode); the stats counters are locked
    so observer threads (#stats requests) read them safely.
    """

    def __init__(self, store: SlotStore, loss: Optional[LossSpec] = None):
        self.store = store
        self.loss = loss if loss is not None \
            else create_loss("fm", store.param.V_dim)
        predict_step = make_predict_fn(store.fns, self.loss)
        # serve-path gather traffic: u_cap fused rows in+out per dispatch
        # (updaters.gather_bytes; docs/observability.md catalog)
        from ..obs import counter
        self._gather_c = counter(
            "store_gather_bytes_total",
            "slot-table row bytes gathered+scattered per dispatched "
            "device program").labels(path="serve")

        def packed_predict(state, i32, f32, b_cap, nnz_cap, u_cap, binary):
            batch, slots, _ = unpack_batch(i32, f32, b_cap, nnz_cap, u_cap,
                                           binary=binary)
            return predict_step(state, batch, slots)

        # jaxtrace.jit: identical to jax.jit when DIFACTO_JAXTRACE is
        # off; traced, this is THE serve jit site the tier-1 gate holds
        # to "zero steady-state recompiles" (analysis/jaxflow.py)
        self._packed = jaxtrace.jit(packed_predict,
                                    static_argnums=(3, 4, 5, 6))
        # fs-sharded stores (serve_mesh_fs > 1): batch buffers ride
        # replicated over the mesh so the jitted gather pulls key-range
        # rows across shards; flat stores keep the plain asarray put
        if store.mesh is not None:
            from ..parallel import put_global, replicated
            repl = replicated(store.mesh)
            self._put = lambda a: put_global(np.asarray(a), repl)
        else:
            self._put = jnp.asarray
        self._shapes = ShapeSchedule()
        self._mu = mutex()
        self._buckets: dict = {}   # statics key -> dispatch count
        self._dispatches = 0
        self._warmed = 0           # buckets compiled by warm_bucket()
        # hot-reload bookkeeping (serve/reload.py swaps stores in)
        self.generation = 1

    # ------------------------------------------------------------ stats
    def stats(self) -> dict:
        """{'buckets_compiled', 'bucket_hits', 'dispatches',
        'model_generation'}: compiled grows only at a bucket's first
        occurrence; a steady-state window adds hits only (zero
        recompiles); model_generation advances once per hot reload.
        Warm-replayed buckets (warm_bucket) compiled without consuming a
        dispatch, so they don't deflate the hit count."""
        with self._mu:
            return {
                "buckets_compiled": len(self._buckets),
                "bucket_hits": self._dispatches
                - (len(self._buckets) - self._warmed),
                "dispatches": self._dispatches,
                "model_generation": self.generation,
            }

    # ------------------------------------------------------- warm replay
    def warm_set(self) -> Tuple[dict, list]:
        """(shape-cap snapshot, compiled bucket keys) — everything a
        blue/green successor needs to pre-compile the exact programs this
        executor serves with (serve/reload.py): the caps make future
        batches pad to the same buckets, the keys are the buckets to
        compile before the swap."""
        with self._mu:
            return self._shapes.snapshot(), list(self._buckets)

    def seed_caps(self, caps: dict) -> None:
        """Adopt another executor's sticky shape caps, so every batch
        shape the predecessor served maps to the same bucket here (a
        batch that was a HIT there stays a hit after the swap)."""
        self._shapes.absorb(caps)

    def warm_bucket(self, key: Tuple[int, int, int, bool]) -> None:
        """Compile the predict program for one recorded bucket key by
        dispatching a synthetic single-row batch padded to its caps —
        identical statics to a real dispatch, so the jit cache entry a
        later request needs already exists. Registers the key without
        counting a dispatch (stats arithmetic stays honest)."""
        b_cap, nnz_cap, u_cap, binary = key
        store = self.store
        blk = RowBlock(
            offset=np.array([0, 1], dtype=np.int64),
            label=np.zeros(1, dtype=np.float32),
            index=np.zeros(1, dtype=np.uint32),
            value=None if binary else np.ones(1, dtype=np.float32),
            weight=None)
        padded = pad_slots_oob(np.zeros(1, dtype=np.int32), u_cap,
                               store.state.capacity)
        i32, f32, _ = pack_batch(blk, 1, padded, b_cap, nnz_cap, u_cap)
        # lint: ok(jax-recompile) warm replay iterates PREVIOUSLY
        # RECORDED bucket keys (warm_set) — a subset of the compiled
        # set by construction, so no key here is ever a fresh compile
        # on the predecessor's model and at most one on the successor's
        pred, _, _ = self._packed(store.state, self._put(i32),
                                  self._put(f32), b_cap, nnz_cap, u_cap,
                                  binary)
        jax.block_until_ready(pred)
        with self._mu:
            if key not in self._buckets:
                self._buckets[key] = 0
                self._warmed += 1

    # ------------------------------------------------------------- swap
    def swap_store(self, store: SlotStore) -> int:
        """Atomically swap a freshly-loaded store under the executor (the
        serve hot-reload commit point). The jitted programs were built
        from make_fns(param) — pure functions of the updater params — so
        the replacement must match the geometry they were compiled
        against; a mismatched reload is rejected here (the old model
        keeps serving) and the caller routes it through the blue/green
        second-executor swap instead (serve/reload.py). The swap itself
        is one attribute assignment: ``predict`` snapshots ``self.store``
        once per call, so in-flight batches finish on the model they
        started with."""
        from .model import store_geometry
        old = self.store
        if store_geometry(store.param) != store_geometry(old.param):
            raise ValueError(
                f"hot-reload geometry mismatch: serving "
                f"(V_dim={old.param.V_dim}, "
                f"hash_capacity={old.param.hash_capacity}) vs new model "
                f"(V_dim={store.param.V_dim}, "
                f"hash_capacity={store.param.hash_capacity}); in-place "
                "swap keeps the compiled programs, so a geometry change "
                "must go through the blue/green executor swap "
                "(serve/reload.py, requires a server-attached reloader)")
        if store.fs_count != old.fs_count:
            # the compiled predict programs bake the table's sharding
            # layout; a different fs degree is a geometry change too
            raise ValueError(
                f"hot-reload geometry mismatch: serving an "
                f"fs={old.fs_count}-sharded table, new store is "
                f"fs={store.fs_count}; pass the same serve_mesh_fs on "
                "the reload path (run_serve threads it automatically) "
                "or go through the blue/green executor swap")
        with self._mu:
            # lint: ok(data-race) atomic reference swap (hot-reload commit
            # point): predict/warm snapshot self.store once per call
            self.store = store
            self.generation += 1
            return self.generation

    # ---------------------------------------------------------- predict
    def predict(self, blk: RowBlock) -> Tuple[np.ndarray, jnp.ndarray,
                                              jnp.ndarray]:
        """Score a raw-id row block. Returns (scores, objv, auc): scores
        are the clamped raw margins for the real rows (host numpy),
        objv/auc stay on device for deferred fetch."""
        if blk.size == 0:
            z = jnp.float32(0.0)
            return np.zeros(0, dtype=np.float32), z, z
        # ONE store snapshot per batch: a concurrent hot-reload swap
        # (swap_store) must never split a batch across two models —
        # in-flight batches finish on the store they started with
        store = self.store
        cblk, uniq, _ = compact(blk)
        # read-only mapping: never insert (unknown ids -> TRASH row 0,
        # whose weights are zero); sort + dedup the slot set because the
        # device kernels declare sorted unique indices, and rewrite the
        # localized columns through the permutation (the host-dedup
        # contract, store.map_keys_dedup)
        slots = store.map_keys(uniq, insert=False)
        uniq_slots, remap = np.unique(slots, return_inverse=True)
        cblk = RowBlock(offset=cblk.offset, label=cblk.label,
                        index=remap[cblk.index].astype(np.uint32),
                        value=cblk.value, weight=cblk.weight)
        n_uniq = len(uniq_slots)
        b_cap = self._shapes.cap("serve.b", blk.size)
        nnz_cap = self._shapes.cap("serve.nnz", blk.nnz)
        # bucket's ladder, not the trainer's finer row_cap: each rung here
        # is a compile on the request path and warm_bucket pre-compiles
        # the set, so four times the rungs is four times the warm-up
        u_cap = self._shapes.cap("serve.u", n_uniq)
        padded = pad_slots_oob(uniq_slots.astype(np.int32), u_cap,
                               store.state.capacity)
        i32, f32, binary = pack_batch(cblk, n_uniq, padded, b_cap, nnz_cap,
                                      u_cap)
        key = (b_cap, nnz_cap, u_cap, binary)
        with self._mu:
            self._buckets[key] = self._buckets.get(key, 0) + 1
            self._dispatches += 1
        from ..updaters.sgd_updater import gather_bytes
        self._gather_c.inc(gather_bytes(store.param, store.state.capacity,
                                        u_cap))
        # lint: ok(jax-recompile) `binary` is a bool from pack_batch —
        # two compile keys by construction (the caps above are proven)
        pred, objv, auc = self._packed(store.state, self._put(i32),
                                       self._put(f32), b_cap, nnz_cap,
                                       u_cap, binary)
        # the ONE declared device->host sync of the serve dispatch loop:
        # scores must reach the response formatter; objv/auc stay on
        # device for deferred fetch. DIFACTO_JAXTRACE counts this site,
        # and the tier-1 gate asserts it is the only one.
        return jaxtrace.fetch(pred, point="serve.scores")[:blk.size], \
            objv, auc

    def predict_scores(self, blk: RowBlock) -> np.ndarray:
        """Scores only — the micro-batcher's entry."""
        return self.predict(blk)[0]
