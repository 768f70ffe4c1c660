"""Multi-host (multi-controller) support over DCN.

The reference scales out with dmlc-tracker launchers + ps-lite rendezvous
(launch.py, SURVEY §2.10/§5.8). The TPU-native equivalent is JAX
multi-controller SPMD: every host runs the same program,
``jax.distributed.initialize`` performs the rendezvous (the Postoffice
analog), ``jax.devices()`` then spans all hosts, and the existing mesh
shardings (parallel/mesh.py) place collectives on ICI within a pod and DCN
across pods — no learner code changes.

Host-side data parallelism keeps the reference's contract: each host reads
its own byte-range file parts (``host_part`` -> Reader(part_idx,
num_parts)), the WorkloadPool semantics move one level up.

For the model state to be identical across controllers the feature ->
slot mapping must be host-consistent. Both store modes achieve it:
the hashed store (store/local.py ``hash_capacity``) maps ids to slots by
stateless modular hashing of the byte-reversed id (SURVEY §7
"fixed-capacity hashed embedding table"); the exact-id dictionary store
rides the synchronized schedule's control plane — the per-step exchange
ships raw uint64 ids and every host inserts the identical sorted union
into its dictionary in the same order, so replica id->slot maps stay
bit-identical with no extra rounds (learners/sgd.py exchange(); the
reference's servers key the model by exact 64-bit id the same way,
src/sgd/sgd_updater.h:141-176).
"""

from __future__ import annotations

import logging
import os
from typing import Optional, Tuple

log = logging.getLogger("difacto_tpu")

# exit code of a rank that joined the rendezvous and then could not bind
# a device (outside fault.exit_code_for's 101..127 dead-peer band)
NO_DEVICE_EXIT_CODE = 3


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """jax.distributed rendezvous; None args resolve from the standard env
    (JAX's own vars, or DIFACTO_COORDINATOR / DIFACTO_NPROCS /
    DIFACTO_RANK as set by launch.py)."""
    import jax
    coordinator_address = coordinator_address or os.environ.get(
        "DIFACTO_COORDINATOR")
    if num_processes is None and "DIFACTO_NPROCS" in os.environ:
        num_processes = int(os.environ["DIFACTO_NPROCS"])
    if process_id is None and "DIFACTO_RANK" in os.environ:
        process_id = int(os.environ["DIFACTO_RANK"])
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id)
    try:
        n_devices = len(jax.devices())
    except RuntimeError as e:
        # rendezvous done, but this process cannot bind a device — on one
        # TPU host, because a peer process holds the chip (one process
        # per chip). The peers are now waiting for this process's
        # devices, and a normal interpreter exit would wait for THEM in
        # jax.distributed's shutdown barrier: nobody leaves. Leave hard
        # and non-zero, so the launcher sees a dead rank and takes the
        # job down (launch.py _run_once).
        log.error("rank %s cannot bind a device after the rendezvous: %s",
                  process_id, e)
        logging.shutdown()
        os._exit(NO_DEVICE_EXIT_CODE)
    log.info("multi-host initialized: process %d of %d, %d global devices",
             jax.process_index(), jax.process_count(), n_devices)


def allgather_np(arr) -> "np.ndarray":
    """Gather a fixed-shape host numpy array from every process ->
    [n_procs, *shape]. The DCN control channel of the synchronized-step
    schedule (the analog of ps-lite's scheduler barrier + key exchange,
    src/store/kvstore_dist.h:61-70). Single process: adds the leading axis.

    NOTE this gather is itself a DEVICE program (process_allgather jits a
    collective over the global devices), so it must be issued in exactly
    the same order as every other device program on every host — only
    call it from the thread that dispatches the device steps. A lookahead
    thread must use :func:`control_allgather_np` instead.
    """
    import jax
    import numpy as np
    _fire_dcn_fault()
    if jax.process_count() == 1:
        return np.asarray(arr)[None]
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(np.asarray(arr)))


def _fire_dcn_fault() -> None:
    """Chaos-harness injection point ``dcn.collective``: traversed before
    every cross-host control exchange (device or KV-store flavor), BEFORE
    the single-process early return so chaos tests exercise it without a
    cluster. ``err`` models a dead coordinator / partitioned DCN link
    surfacing as the same OSError a real gRPC failure raises; fires count
    into ``faults_fired_total{point,kind}``."""
    from ..utils import faultinject
    faultinject.act_default(faultinject.fire("dcn.collective"))


# --------------------------------------------------------------- control
# Deviceless control plane over the jax.distributed KV store.

_CTRL_TIMEOUT_MS = 600_000
_ctrl_seq = 0
_ctrl_bar = 0
_ctrl_written: list = []


def control_allgather_np(arr) -> "np.ndarray":
    """Deviceless allgather over the jax.distributed KV store (pure gRPC
    to the coordinator — the ps-lite-analog wire, SURVEY §5.8).

    Unlike :func:`allgather_np`, this touches NO device, so the SPMD
    schedule may run it from a lookahead thread and overlap the DCN
    round trip with device execution (learners/sgd.py ``exchange()``).
    Interleaving a device-collective allgather with the step stream from
    two threads deadlocks — hosts would enqueue the same device programs
    in different orders (measured: a 2-process virtual-mesh run hangs at
    epoch 1 once compiles stop serializing the race).

    All processes must call this the same number of times with the same
    shape/dtype (one lookahead thread per host preserves that). Keys
    accumulate in the coordinator until :func:`control_cleanup`.
    """
    import jax
    import numpy as np
    global _ctrl_seq
    _fire_dcn_fault()
    from ..obs import REGISTRY
    REGISTRY.counter(
        "dcn_collectives_total",
        "cross-host control-plane exchanges issued").inc()
    a = np.ascontiguousarray(np.asarray(arr))
    if jax.process_count() == 1:
        return a[None]
    from jax._src import distributed
    client = distributed.global_state.client
    rank, n = jax.process_index(), jax.process_count()
    key = f"difacto/ctrl/{_ctrl_seq}"
    _ctrl_seq += 1
    client.key_value_set_bytes(f"{key}/{rank}", a.tobytes())
    _ctrl_written.append(f"{key}/{rank}")
    out = np.empty((n,) + a.shape, a.dtype)
    for r in range(n):
        if r == rank:
            out[r] = a
        else:
            b = client.blocking_key_value_get_bytes(f"{key}/{r}",
                                                    _CTRL_TIMEOUT_MS)
            out[r] = np.frombuffer(b, a.dtype).reshape(a.shape)
    return out


def _fire_push_stale() -> None:
    """Chaos-harness injection point ``push.stale``: traversed when a
    host PUBLISHES its step clock under a bounded-delay (τ>0) window —
    the moment a delayed gradient push becomes visible to peers that may
    already be up to τ steps ahead. Fired BEFORE the single-process
    early return so chaos tests exercise the stale-push path without a
    cluster; fires count into ``faults_fired_total{point,kind}``."""
    from ..utils import faultinject
    faultinject.act_default(faultinject.fire("push.stale"))


# Bounded-delay (τ) step clocks for the windowed exchange
# (learners/sgd.py _iterate_data_spmd). Each host POSTS its clock after
# dispatching step t (non-blocking KV set); a host whose exchange
# pipeline would exceed the τ-window blocks on the SPECIFIC peer clock
# key it needs (present => the get returns immediately, else it blocks
# until the peer posts) — a pairwise wait, not a symmetric collective,
# so hosts need not agree on how many waits each issues and the
# protocol is deadlock-free (every wait targets a strictly earlier
# step). Keys are namespaced by the launcher's restart attempt
# (fault.restart_attempt): a relaunched cluster rejoins at a fresh
# clock epoch consistent across all survivors, never observing the
# previous attempt's stale clocks. Clock keys ride ``_ctrl_written``
# and are reclaimed by :func:`control_cleanup` at the part drain.

_clock_gen = 0


def clock_open() -> int:
    """New clock generation for one windowed part. Every host opens
    generations in the same order (the part loop is the same program),
    so the returned ids agree across hosts without communication."""
    global _clock_gen
    _clock_gen += 1
    return _clock_gen


def post_clock(gen: int, t: int) -> None:
    """Publish "this host has dispatched windowed step ``t``" (steps
    number from 0 within generation ``gen``). Non-blocking."""
    import jax
    _fire_push_stale()
    if jax.process_count() == 1:
        return
    from .fault import restart_attempt
    from jax._src import distributed
    client = distributed.global_state.client
    key = (f"difacto/clock/{restart_attempt()}/{gen}/"
           f"{jax.process_index()}/{t}")
    client.key_value_set_bytes(key, b"1")
    _ctrl_written.append(key)


def wait_clock(gen: int, peer: int, t: int) -> float:
    """Block until ``peer`` has posted windowed step ``t`` of generation
    ``gen``; returns the seconds spent blocked (0.0 when the clock was
    already posted, and always on a single process). Callers route this
    through the dead-host monitor (``monitor.guarded``) so a peer dying
    mid-wait aborts for restart instead of hanging to the timeout."""
    import time as _time

    import jax
    if jax.process_count() == 1:
        return 0.0
    from .fault import restart_attempt
    from jax._src import distributed
    client = distributed.global_state.client
    key = f"difacto/clock/{restart_attempt()}/{gen}/{peer}/{t}"
    t0 = _time.monotonic()
    client.blocking_key_value_get_bytes(key, _CTRL_TIMEOUT_MS)
    return _time.monotonic() - t0


def control_cleanup() -> None:
    """Delete this process's control keys once every peer has consumed
    them. Call at a quiesce point all hosts reach together (the part
    drain in the SPMD schedule); the barrier makes consumption global
    before deletion, keeping the coordinator's KV memory bounded by one
    part's payloads instead of the whole run's."""
    import jax
    global _ctrl_bar
    if jax.process_count() == 1:
        _ctrl_written.clear()
        return
    from jax._src import distributed
    client = distributed.global_state.client
    bar = _ctrl_bar
    _ctrl_bar += 1
    client.wait_at_barrier(f"difacto/ctrlbar/{bar}", _CTRL_TIMEOUT_MS)
    for k in _ctrl_written:
        client.key_value_delete(k)
    _ctrl_written.clear()


def to_local_numpy(arr) -> "np.ndarray":
    """Assemble a (possibly multi-host) jax.Array into a full host numpy
    array from this process's addressable shards.

    Valid when every piece of the array is present on some local device —
    true for our layout, where the table is sharded over the intra-host
    ``fs`` axis and replicated over the cross-host ``dp`` axis. np.asarray
    would refuse (the sharding spans non-addressable devices) even though
    the data is all here.
    """
    import numpy as np
    if getattr(arr, "is_fully_addressable", True):
        return np.asarray(arr)
    out = np.empty(arr.shape, dtype=arr.dtype)
    seen = np.zeros(arr.shape[0] if arr.ndim else 1, dtype=bool)
    for sh in arr.addressable_shards:
        out[sh.index] = np.asarray(sh.data)
        seen[sh.index[0] if sh.index else slice(None)] = True
    if not seen.all():
        raise ValueError(
            "array is not host-complete: some shards live only on other "
            "hosts (expected fs-sharded-within-host layout)")
    return out


def local_rows(arr, lo: int, hi: int) -> "np.ndarray":
    """Rows [lo, hi) of a (possibly dp-sharded) global array, assembled
    from this process's addressable shards — np.asarray would refuse on a
    multi-host sharding even though these rows live here."""
    import numpy as np

    from ..utils import jaxtrace
    if getattr(arr, "is_fully_addressable", True):
        # declared device->host sync (jaxtrace counts it): callers slice
        # prediction rows out for the pred writer
        return jaxtrace.fetch(arr, point="multihost.local_rows")[lo:hi]
    out = np.zeros((hi - lo,) + arr.shape[1:], dtype=arr.dtype)
    filled = np.zeros(hi - lo, dtype=bool)
    for sh in arr.addressable_shards:
        sl = sh.index[0] if sh.index else slice(None)
        start = sl.start or 0
        stop = arr.shape[0] if sl.stop is None else sl.stop
        s, e = max(start, lo), min(stop, hi)
        if s < e:
            data = np.asarray(sh.data)
            out[s - lo:e - lo] = data[s - start:e - start]
            filled[s - lo:e - lo] = True
    if not filled.all():
        raise ValueError(
            f"rows [{lo}, {hi}) are not all addressable on this host")
    return out


def host_part() -> Tuple[int, int]:
    """(part_idx, num_parts) for this host's share of the input files —
    the multi-controller analog of the reference's Rank()/NumWorkers()
    reader sharding (src/lbfgs/lbfgs_learner.cc:148-150)."""
    import jax
    try:
        return jax.process_index(), jax.process_count()
    except RuntimeError:
        return 0, 1


def global_kv_union(ids, cnts):
    """Union per-host sorted-unique (id, count) dictionaries across all
    processes: counts sum, ids union (the reference's servers own one
    global key space). uint64 ids ride the DCN gather as uint32 pairs —
    process_allgather goes through jax, which silently truncates uint64
    with x64 disabled. Single process: returns the inputs."""
    import numpy as np

    from ..ops.kv import kv_union
    sizes = allgather_np(np.array([len(ids)], dtype=np.int32))[:, 0]
    cap = int(sizes.max())
    ids_p = np.zeros(cap, dtype=np.uint64)
    ids_p[:len(ids)] = ids
    cnt_p = np.zeros(cap, dtype=np.float32)
    cnt_p[:len(cnts)] = cnts
    all_ids = allgather_np(ids_p.view(np.uint32))
    all_cnt = allgather_np(cnt_p)
    out_ids = np.empty(0, dtype=ids.dtype)
    out_cnt = np.empty(0, dtype=np.float32)
    for h in range(len(sizes)):
        k = int(sizes[h])
        h_ids = np.ascontiguousarray(
            all_ids[h]).view(np.uint64)[:k].astype(ids.dtype)
        out_ids, out_cnt = kv_union(out_ids, out_cnt, h_ids, all_cnt[h, :k])
    return out_ids, out_cnt


def allreduce_np(buf, monitor=None, sum_dtype=None):
    """Sum a host array across all processes over DCN.

    64-bit dtypes ride the wire as uint32 views — the jax transport
    canonicalizes 64-bit to 32-bit with x64 disabled, which would
    silently truncate them (same hazard global_kv_union guards for ids).
    ``sum_dtype`` widens the host-side summation (e.g. gather float32
    partials, accumulate in float64). ``monitor`` arms the dead-host
    watchdog around the collective (parallel/fault.py).

    This is allgather-based (every host materializes [n_hosts, len]); at
    very large vector sizes a device psum over a global mesh would halve
    the wire cost, but the control plane deliberately avoids requiring a
    collective mesh.
    """
    import numpy as np
    buf = np.ascontiguousarray(buf)
    wide = buf.dtype.itemsize == 8
    wire = buf.view(np.uint32) if wide else buf
    if monitor is not None:
        g = monitor.guarded(allgather_np, wire)
    else:
        g = allgather_np(wire)
    if wide:
        g = np.ascontiguousarray(g).view(buf.dtype)
    return g.sum(axis=0, dtype=sum_dtype)
