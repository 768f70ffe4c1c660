"""Producer-side batch packing, shared by threads AND worker processes.

The hashed-store host pipeline (read -> parse -> localize -> slot-map ->
panel/COO pack) is stateless, so it can run anywhere: on the learner's
producer THREADS (data/producer_pool.OrderedProducerPool) or in spawned
worker PROCESSES (ProcessProducerPool) that ship packed payloads through
the shared-memory ring (data/shm_ring.py). This module is the single
definition of that pipeline — extracted from learners/sgd.py so the two
transports can never diverge on the payload contract (tuple order, shape-
cap keys, counts-section semantics).

Process workers rebuild the pipeline from a picklable :class:`StreamSpec`
(``functools.partial(spec_iter, spec)`` is the pool's ``make_iter``); the
spec carries a snapshot of the consumer's sticky shape caps so workers
start from the same shape schedule and steady-state epochs keep replaying
one compiled step per layout.
"""

from __future__ import annotations

import dataclasses
import threading
from dataclasses import dataclass, field
from typing import Iterator, Optional, Sequence

import numpy as np
from ..utils.locktrace import mutex


class ShapeSchedule:
    """Per-run sticky shape caps: every batch pads to the largest bucket
    seen so far for its (job, dim) key, so steady-state epochs replay ONE
    compiled step instead of re-bucketing per batch (per-batch ``bucket()``
    put every odd-sized tail in a fresh jit cache entry, and the compiles
    dominated the whole epoch). A
    growing batch costs at most log-many recompiles over the run; caps
    never shrink. Thread-safe: producer threads prepare batches
    concurrently. ``snapshot``/``absorb`` ship the caps across the process
    boundary: spawned producer workers seed from the consumer's snapshot,
    and the consumer absorbs the caps each delivered payload was packed at,
    so a cap grown in one worker reaches every later epoch's workers."""

    def __init__(self) -> None:
        self._caps: dict = {}
        self._lock = mutex()

    def cap(self, key: str, n: int, minimum: int = 8,
            exact: bool = False, ladder=None) -> int:
        """``exact`` keeps a plain sticky max instead of bucketing — for
        dims that are naturally constant (panel width: criteo rows are
        always 39 wide; bucketing to 48 would inflate every panel cell
        stream by ~23% and defeat the uniform-reshape fast path).
        ``ladder`` is the rounding, ``bucket`` unless given
        (:meth:`row_cap` gives the finer one)."""
        from ..ops.batch import bucket
        with self._lock:
            c = self._caps.get(key, 0)
            if n > c or c == 0:
                # floor degenerate dims like the bucket() it replaces
                # (bucket(0) == minimum) — empty batches still need
                # non-zero-sized device shapes
                c = max(n, 1) if exact else (ladder or bucket)(n, minimum)
                self._caps[key] = c
            return c

    def row_cap(self, job: str, n: int) -> int:
        """The sticky cap of ``job``'s unique-row dimension (key
        ``<job>.u``), on ``ops.batch.row_cap``'s ladder of eighths: the
        step program's legs are all sized by it, so its padding is
        device time in every step. Every batch the learner packs takes
        it; the request path (``serve.u``, and one-device ``task=pred``
        through it) and the SPMD slot union keep ``bucket`` — see where
        each takes its cap."""
        from ..ops.batch import row_cap
        return self.cap(job + ".u", n, ladder=row_cap)

    def chunk_cap(self, job: str, n: int, u_cap: int, cells: int,
                  dp_div: int = 1) -> int:
        """The sticky cap of ``job``'s chunk dimension (key ``<job>.c``)
        for a batch that needs ``n`` chunks (ops.batch.chunks_needed), on
        the ladder of ``<job>.u``: the backward gathers L rows and adds
        one partial a chunk, used or not, so its padding is device time
        in every step. Never above the static bound
        ops.batch.chunk_cap(u_cap, cells), which no batch of the shape
        can pass; rounded up to a multiple of ``dp_div`` so the chunk
        arrays shard evenly over a mesh's dp axis.

        Shapes whose static bound is at most ``STATIC_CHUNKS`` keep it:
        there the padding is microseconds, while every rung the sticky
        cap climbs is three compiles (the chunker, the step, the pair
        program) and a staged batch that replays unpaired."""
        from ..ops.batch import chunk_cap, row_cap
        c = chunk_cap(u_cap, cells)
        if c > self.STATIC_CHUNKS:
            c = min(self.cap(job + ".c", n, ladder=row_cap), c)
        return -(-c // dp_div) * dp_div

    # the largest static chunk bound that is used as it is (chunk_cap)
    STATIC_CHUNKS = 8192

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._caps)

    def absorb(self, caps: dict) -> None:
        """Merge already-resolved cap VALUES (no re-bucketing: the values
        are caps, not raw dims)."""
        with self._lock:
            for k, v in caps.items():
                if v > self._caps.get(k, 0):
                    self._caps[k] = v


@dataclass
class BlkInfo:
    """The slice of a RowBlock the consumer's dispatch still needs after
    the payload is packed (duck-typed for learners' ``blk`` argument):
    shipping the whole block across the process boundary would re-send
    the raw CSR arrays the packed payload already encodes."""
    size: int
    label: Optional[np.ndarray] = None


# ------------------------------------------------------------------ pack
def pack_payload(shapes: ShapeSchedule, cblk, n_lanes: int,
                 padded: np.ndarray, b_cap: int, dim_min: int, job: str,
                 counts=None, stream_chunk: bool = False):
    """Shared pack tail of all batch-preparation paths (prepare_hashed /
    prepare_from_uniq / the learner's consumer-side _pack_mapped): panel
    layout when rows are near-uniform, COO otherwise, shape caps from the
    sticky schedule. One definition, so the payload contract (tuple
    order, cap keys) can never diverge between the producer-side and
    consumer-side packers. ``padded`` is the OOB-padded slot vector (its
    length IS u_cap); ``cblk.index`` must already address its
    sorted-unique lanes (host dedup)."""
    from ..ops.batch import pack_batch, pack_panel, panel_width
    u_cap = len(padded)
    width = panel_width(cblk, b_cap)
    if width is not None:
        width = shapes.cap(job + ".w", width, exact=True)
        i32, f32, binary = pack_panel(
            cblk, n_lanes, padded, b_cap, width, u_cap,
            counts=counts)
        if stream_chunk:
            return ("panel_chunked", i32, f32,
                    chunk_host(shapes, job, i32, f32, b_cap, width, u_cap,
                               binary),
                    binary, b_cap, width, u_cap)
        return ("panel", i32, f32, binary, b_cap, width, u_cap)
    nnz_cap = shapes.cap(job + ".nnz", cblk.nnz, dim_min)
    i32, f32, binary = pack_batch(
        cblk, n_lanes, padded, b_cap, nnz_cap, u_cap,
        counts=counts)
    return ("coo", i32, f32, binary, b_cap, nnz_cap, u_cap)


def payload_rows(payload) -> int:
    """The distinct table rows of a packed host payload: the
    ``num_uniq`` word its packer left in the i32 buffer's meta tail
    (ops/batch.pack_panel, pack_panel_raw: ``[b, nu]``; pack_batch:
    ``[b, nu, nnz]``)."""
    return int(payload[1][-2 if payload[0] == "coo" else -1])


def payload_chunks(payload, count: bool = False):
    """The chunks a packed host payload's lanes need, or None where
    nobody asked: a producer-chunked payload's used chunks are a prefix
    of its ``chunk_lane``; a plain panel's are counted from its lanes
    (ops.batch.chunks_needed) when ``count`` says a two-tier layout will
    be staged from it."""
    if payload[0] == "panel_chunked":
        u_cap = payload[7]
        return int(np.count_nonzero(payload[3][1] < u_cap))
    if count and payload[0] == "panel":
        from ..ops.batch import chunks_needed
        b_cap, width, u_cap = payload[4:7]
        return chunks_needed(payload[1][:b_cap * width], u_cap)
    return None


def chunk_host(shapes: ShapeSchedule, job: str, i32: np.ndarray,
               f32: np.ndarray, b_cap: int, width: int, u_cap: int,
               binary: bool):
    """Producer-side two-tier chunked-run layout for a packed panel (the
    host twin of the learner's staging-time device chunker): streamed
    runs then dispatch the fast chunked step instead of the unsorted
    scatter. The chunk cap is the sticky ``<job>.c`` of ``shapes``.
    Ragged panels always carry explicit values (zero on pad cells,
    ops/batch._panel_arrays), so pad tokens contribute nothing through
    chunk_vals or head_vals; uniform binary panels have no pad cells."""
    from ..ops.batch import chunks_needed, panel_chunk_tokens_np
    cells = b_cap * width
    fv = None if binary else f32[:cells]
    C = shapes.chunk_cap(job, chunks_needed(i32[:cells], u_cap), u_cap,
                         cells)
    return panel_chunk_tokens_np(i32[:cells], fv, u_cap, b_cap, width,
                                 C=C, head=True)


def _count_distinct(tok: np.ndarray, hash_capacity: int) -> int:
    """Exact distinct-token count WITHOUT the sort ``np.unique`` pays:
    an O(nnz + capacity) flag pass when the capacity-sized bool array
    is cheap, the sort fallback above that (still skips the inverse
    map + O(nnz) remap, the other half of the host dedup cost). Sizes
    the device-dedup path's sticky u-cap (prepare_hashed)."""
    if hash_capacity <= (1 << 24):
        seen = np.zeros(hash_capacity, dtype=bool)
        seen[tok] = True
        return int(seen.sum())
    return len(np.unique(tok))


def prepare_hashed(shapes: ShapeSchedule, hash_capacity: int, blk,
                   want_counts: bool, fill_counts: bool, dim_min: int,
                   job: str, b_cap: Optional[int] = None,
                   stream_chunk: bool = False,
                   device_dedup: bool = False,
                   admit=None):
    """Producer batch preparation for the hashed store: ONE int32
    np.unique collapses localization (Localizer::Compact), key->slot
    mapping, and collision dedup, then the batch packs into the
    two-buffer transfer — panel layout when rows are near-uniform
    (criteo), COO otherwise. Stateless, so safe off-thread AND
    off-process. ``b_cap`` pins the row cap; the remaining dims ride the
    sticky shape schedule keyed by ``job`` so epochs never recompile.
    ``want_counts`` keeps the packed counts section (and thus the step's
    jit signature) present for the WHOLE run; ``fill_counts`` (epoch 0
    only) computes real occurrence counts — later epochs ship an all-zero
    section, making apply_count a no-op instead of a recompile.

    ``device_dedup`` (ISSUE 13): ship RAW hashed token lanes and let the
    jit step run the sort + run-length dedup on device
    (ops/fused.dedup_tokens) — the host pays only the hash and an
    O(nnz + capacity) distinct-count flag pass (_count_distinct), not
    the O(nnz log nnz) sort + inverse + remap. Engages only on
    panel-shaped TRAINING batches past the count push (fill_counts
    forces the host path: counts need the host inverse) — COO-shaped
    batches fall back to host dedup. The u-cap is sized with a +1
    margin because pad cells introduce the TRASH lane on device.

    ``admit`` (capacity/sketch.AdmissionFilter, ISSUE 19): count-min
    admission over the hashed token stream — unadmitted occurrences
    remap to the OOB sentinel (== hash_capacity) and, being the largest
    "slot", sort LAST among the real slots; the sentinel lane is dropped
    below so the unique declaration stays truthful (cells referencing it
    fall onto the first OOB pad lane: gathers zeros, scatter dropped).
    Admission forces the host-dedup path — the sentinel cannot ride raw
    device lanes (the on-device sorter would give it a real lane)."""
    from ..base import reverse_bytes
    from ..store.local import hash_slots, pad_slots_oob

    tok = hash_slots(reverse_bytes(blk.index), hash_capacity)
    if admit is not None:
        tok = admit.filter(tok)
    if admit is None and device_dedup and not fill_counts:
        from ..ops.batch import pack_panel_raw, panel_width
        b_cap_raw = b_cap or shapes.cap(job + ".b", blk.size, dim_min)
        cblk = dataclasses.replace(blk, index=tok.astype(np.uint32))
        width = panel_width(cblk, b_cap_raw)
        if width is not None:
            n_uniq = _count_distinct(tok, hash_capacity)
            u_cap = shapes.row_cap(job, n_uniq + 1)
            width = shapes.cap(job + ".w", width, exact=True)
            i32, f32, binary = pack_panel_raw(cblk, n_uniq, b_cap_raw,
                                              width)
            return ("panel_raw", i32, f32, binary, b_cap_raw, width,
                    u_cap)
    if fill_counts:
        slots, inverse, counts = np.unique(
            tok, return_inverse=True, return_counts=True)
        counts = counts.astype(np.float32)
    else:
        slots, inverse = np.unique(tok, return_inverse=True)
        counts = np.zeros(0, np.float32) if want_counts else None
    if admit is not None and len(slots) and slots[-1] == admit.sentinel:
        # drop the sentinel lane: cells that referenced it now index the
        # first OOB pad position instead (pad value = hash_capacity +
        # position, pad_slots_oob) — still a zero-gather, dropped-scatter
        # lane, and the slots section stays unique
        slots = slots[:-1]
        if fill_counts:
            counts = counts[:-1]
    cblk = dataclasses.replace(blk, index=inverse.astype(np.uint32))
    n_uniq = len(slots)
    # +1 under admission: cells whose token was unadmitted reference
    # position n_uniq, which must exist as an OOB pad lane even when the
    # sticky cap is otherwise exactly full
    u_cap = shapes.row_cap(job, n_uniq + (1 if admit is not None else 0))
    b_cap = b_cap or shapes.cap(job + ".b", blk.size, dim_min)
    padded = pad_slots_oob(slots.astype(np.int32), u_cap, hash_capacity)
    return pack_payload(shapes, cblk, n_uniq, padded, b_cap, dim_min,
                        job, counts=counts, stream_chunk=stream_chunk)


def prepare_from_uniq(shapes: ShapeSchedule, hash_capacity: int, cblk,
                      uniq, counts, want_counts: bool, fill_counts: bool,
                      dim_min: int, job: str, b_cap: Optional[int] = None,
                      stream_chunk: bool = False):
    """Cached fast path (data/cached.py): the block arrives already
    localized to ``uniq`` (sorted reversed ids). The slot map + dedup is
    O(uniq); the O(nnz) index gather through the uniq->slot permutation
    runs HERE, once, on the producer. Shape caps come from the sticky
    schedule; the counts section stays present all run (see
    prepare_hashed)."""
    from ..store.local import hash_slots, pad_slots_oob

    raw = hash_slots(uniq, hash_capacity)
    slots, remap = np.unique(raw, return_inverse=True)
    cblk = dataclasses.replace(
        cblk, index=remap[cblk.index].astype(np.uint32))
    n_lanes = len(slots)
    u_cap = shapes.row_cap(job, n_lanes)
    b_cap = b_cap or shapes.cap(job + ".b", cblk.size, dim_min)
    scounts = np.zeros(0, np.float32) if want_counts else None
    if fill_counts and counts is not None:
        # counts are per uniq lane; aggregate to slot space (colliding
        # lanes sum, mirroring map_keys_dedup)
        scounts = np.zeros(u_cap, dtype=np.float32)
        scounts[:n_lanes] = np.bincount(
            remap, weights=counts, minlength=n_lanes)
    padded = pad_slots_oob(slots.astype(np.int32), u_cap, hash_capacity)
    return pack_payload(shapes, cblk, n_lanes, padded, b_cap, dim_min,
                        job, counts=scounts, stream_chunk=stream_chunk)


# ------------------------------------------------------------------ spec
@dataclass
class StreamSpec:
    """Everything a spawned producer worker needs to rebuild
    ``make_iter(part)`` for the hashed streamed-training path — plain
    picklable values only (no learner, no store, no device state)."""
    parts: Sequence[int]        # logical pool index -> actual part id
    n_jobs: int
    host_rank: int
    num_hosts: int
    data_in: str
    data_format: str
    cached_uri: Optional[str]
    batch_size: int
    shuffle: int
    neg_sampling: float
    epoch: int
    hash_capacity: int
    want_counts: bool
    fill_counts: bool
    dim_min: int
    job: str
    b_cap: Optional[int]
    stream_chunk: bool
    need_label: bool
    # ship raw hashed token lanes; the jit step dedups on device
    # (prepare_hashed device_dedup — ISSUE 13)
    device_dedup: bool = False
    # count-min admission threshold + sketch seed base (ISSUE 19,
    # capacity/sketch.make_admission): workers rebuild the SAME
    # per-(seed, epoch, part) filter the thread-mode producer builds, so
    # both transports admit identical token sets
    admit_min_count: int = 0
    admit_seed: int = 0
    caps: dict = field(default_factory=dict)
    # the consumer's trace id (obs/trace.py): spawned workers adopt it so
    # their parse/pack spans join the parent's timeline in one trace file
    trace_id: int = 0


def timed_reader(it: Iterator, registry, part: int) -> Iterator:
    """Yield from ``it`` accounting each blocking ``next`` to the PARSE
    stage of ``registry`` (obs.stage: seconds into the counter + one
    ``producer.parse`` span per batch, same start and end) — the read +
    parse half of the pipeline, as opposed to the pack half timed at the
    prepare call. One definition for threads and worker processes, so
    bench's stage table means the same thing in both transports."""
    from ..obs import names, stage
    it = iter(it)
    while True:
        with stage(registry, names.PARSE, part=part):
            item = next(it, None)
        if item is None:
            return
        yield item


def spec_iter(spec: StreamSpec, part_i: int) -> Iterator:
    """The process-mode ``make_iter``: yields the same ("ready", blk_info,
    payload) items the learner's thread-mode make_iter produces for the
    hashed fast path, deterministically (seeded per (epoch, part) — the
    retry/re-issue contract). Heavy imports happen here, in the worker,
    after its env overrides are applied.

    Instrumented against the worker's process-global obs registry
    (stage_seconds_total{stage=parse|pack}, producer rows/batches); the
    pool ships its snapshot back to the consumer (obs/proc.py), which is
    how the stage decomposition survives the process boundary."""
    from ..obs import REGISTRY, names, stage, trace
    if spec.trace_id:
        trace.set_trace_id(spec.trace_id)
    rows_c = REGISTRY.counter("producer_rows_total",
                              "rows produced by the streamed pipeline")
    batches_c = REGISTRY.counter("producer_batches_total",
                                 "batches produced by the streamed pipeline")
    shapes = ShapeSchedule()
    shapes.absorb(spec.caps)
    part = spec.parts[part_i]
    g_idx = spec.host_rank * spec.n_jobs + part
    g_num = spec.n_jobs * spec.num_hosts

    def info(blk) -> BlkInfo:
        return BlkInfo(size=blk.size,
                       label=blk.label if spec.need_label else None)

    def packed(fn, *args, **kw):
        with stage(REGISTRY, names.PACK, part=part):
            return fn(*args, **kw)

    if spec.cached_uri is not None:
        from .cached import CachedBatchReader
        rdr = CachedBatchReader(
            spec.cached_uri, g_idx, g_num, spec.batch_size,
            shuffle=spec.shuffle > 0,
            neg_sampling=spec.neg_sampling,
            seed=spec.epoch * max(g_num, 1) + g_idx,
            need_counts=spec.fill_counts)
        for sub, uniq, cnts in timed_reader(rdr, REGISTRY, part):
            rows_c.inc(sub.size)
            batches_c.inc()
            yield ("ready", info(sub), packed(
                prepare_from_uniq, shapes, spec.hash_capacity, sub, uniq,
                cnts, spec.want_counts, spec.fill_counts, spec.dim_min,
                spec.job, spec.b_cap, stream_chunk=spec.stream_chunk))
        return
    from .batch_reader import BatchReader
    from ..capacity.sketch import make_admission
    admit = make_admission(spec.hash_capacity, spec.admit_min_count,
                           spec.admit_seed, spec.epoch, g_idx)
    reader = BatchReader(spec.data_in, spec.data_format, g_idx, g_num,
                         spec.batch_size, spec.batch_size * spec.shuffle,
                         spec.neg_sampling,
                         seed=spec.epoch * max(g_num, 1) + g_idx)
    for blk in timed_reader(reader, REGISTRY, part):
        rows_c.inc(blk.size)
        batches_c.inc()
        yield ("ready", info(blk), packed(
            prepare_hashed, shapes, spec.hash_capacity, blk,
            spec.want_counts, spec.fill_counts, spec.dim_min, spec.job,
            spec.b_cap, stream_chunk=spec.stream_chunk,
            device_dedup=spec.device_dedup, admit=admit))
