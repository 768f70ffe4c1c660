"""Device mesh + sharding layout: the ICI "parameter server".

TPU-native replacement for the reference's distributed topology
(SURVEY §2.9): the worker/server split becomes SPMD over a 2-D
``jax.sharding.Mesh`` with axes

- ``fs`` (feature shards) — the slot table [w, z, sqrt_g, cnt, V, Vg, v_live]
  is sharded along its capacity axis. This is the TPU analog of ps-lite's
  key-range sharding across servers (src/store/kvstore_dist.h:90-118): the
  byte-reversed feature-id space maps to slots, contiguous slot ranges live on
  different devices, and the per-batch gather/scatter of unique rows is the
  Push/Pull — XLA inserts the all-gather / reduce-scatter collectives that
  ps-lite implemented as ZMQ messages.
- ``dp`` (data parallel) — the batch COO arrays are sharded along their
  nnz/row axes, the analog of DiFacto's worker data parallelism
  (file parts dispatched by WorkloadPool, src/tracker/dist_tracker.h:136-156).
  Unlike the reference's *asynchronous* per-worker updates, the TPU step is
  synchronous: all dp shards contribute to one gradient segment-sum
  (SURVEY §7 "hard parts (b)").

All shapes are padded to power-of-two buckets (ops/batch.py), so any mesh with
power-of-two axis sizes divides them evenly.

**The owned run.** A step's unique slots arrive sorted and the table is
sharded by key range (:func:`fs_shard_bounds`), so the rows one fs shard
owns are one contiguous run of the slot vector. Left to GSPMD, every
shard is handed all ``u_cap`` slots: its gather masks the ones it does
not own and its scatter drops them, at the price of the whole vector.
Where the host has counted how many slots the fullest shard owns
(``own_cap``, learners/sgd.py ``_owned_cap``), ops/fused.gather_rows and
scatter_rows instead run inside a ``shard_map`` over ``fs``: each shard
finds where its run starts, slices ``own_cap`` slots, gathers them into
their place in a zero operand (summed over ``fs``: the same exchange) and
scatters its ``own_cap`` new rows in place. Same rows, each read and
written once by the chip that owns it, at about ``1/fs`` of the indices.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DP_AXIS = "dp"
FS_AXIS = "fs"


def make_mesh(dp: int = 1, fs: int = 1,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a (dp, fs) mesh over the first dp*fs available devices.

    Axis sizes must be powers of two: every sharded dimension (slot-table
    capacity, batch/nnz buckets) is padded to a power of two, so only
    power-of-two axes divide them evenly.
    """
    for name, v in ((DP_AXIS, dp), (FS_AXIS, fs)):
        if v < 1 or (v & (v - 1)) != 0:
            raise ValueError(f"mesh axis {name}={v} must be a power of two")
    n = dp * fs
    if devices is None:
        devices = jax.devices()
    if len(devices) < n:
        raise ValueError(f"need {n} devices, have {len(devices)}")
    if jax.process_count() > 1:
        # multi-controller: the fs axis must stay intra-host so every host
        # holds a complete copy of the fs-sharded table (dp replicates it
        # across hosts) — required by checkpointing/evaluate host reads
        # (multihost.to_local_numpy) and by ICI-local table collectives
        lcl = jax.local_device_count()
        if n != len(devices):
            raise ValueError(
                f"multi-host meshes must use every device: dp*fs={n} != "
                f"{len(devices)} global devices")
        if fs > lcl or lcl % fs:
            raise ValueError(
                f"mesh fs={fs} must divide the local device count {lcl} "
                "(the feature-sharded table must be host-complete)")
    arr = np.asarray(devices[:n]).reshape(dp, fs)
    return Mesh(arr, (DP_AXIS, FS_AXIS))


def fs_size(mesh: Optional[Mesh]) -> int:
    """Feature-shard degree of a mesh (1 for no mesh): the number of
    contiguous key-range shards the slot table splits into."""
    return 1 if mesh is None else int(mesh.shape[FS_AXIS])


def validate_fs_capacity(capacity: int, fs: int) -> None:
    """Every sharded dim must divide the fs axis evenly (jax rejects
    uneven NamedShardings): power-of-two capacities always do, but
    ``hash_capacity`` is user-chosen — fail at construction, not at the
    first device_put deep inside a train step."""
    if fs > 1 and capacity % fs:
        raise ValueError(
            f"table capacity {capacity} is not divisible by mesh fs={fs}: "
            "the slot table shards its capacity axis in contiguous "
            "key ranges, one per fs device — pick hash_capacity (or "
            "init_capacity) as a multiple of fs")


def fs_shard_bounds(capacity: int, fs: int):
    """[(lo, hi)] row ranges per fs shard — the contiguous key ranges of
    the table's capacity axis, the TPU analog of ps-lite's per-server
    key ranges (kvstore_dist.h:90-118). Shard i owns slots
    [i*capacity/fs, (i+1)*capacity/fs); per-shard checkpoints
    (store/local.py save) slice and restore exactly these rows."""
    validate_fs_capacity(capacity, fs)
    rows = capacity // fs
    return [(i * rows, (i + 1) * rows) for i in range(fs)]


def state_sharding(mesh: Mesh):
    """NamedSharding pytree spec for SGDState: capacity axis over fs.

    Applied via tree_map by leaf rank: 1-D [C] -> P('fs'),
    2-D [C, k] -> P('fs', None).
    """
    def spec(x):
        nd = np.ndim(x) if not hasattr(x, "ndim") else x.ndim
        return NamedSharding(mesh, P(FS_AXIS, *([None] * (nd - 1))))
    return spec


def batch_sharding(mesh: Mesh):
    """NamedSharding for DeviceBatch leaves: leading axis over dp,
    scalars replicated."""
    def spec(x):
        nd = np.ndim(x) if not hasattr(x, "ndim") else x.ndim
        if nd == 0:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(DP_AXIS, *([None] * (nd - 1))))
    return spec


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def put_global(arr, sharding: NamedSharding):
    """Place a host array under ``sharding``, working across processes.

    Single-process: plain device_put. Multi-process: the sharding spans
    devices this host cannot address, so each process contributes its
    addressable pieces via make_array_from_callback — every host must pass
    the same value (true for replicated inputs and for deterministic
    same-seed state init)."""
    if all(d.process_index == jax.process_index()
           for d in sharding.device_set):
        return jax.device_put(arr, sharding)
    arr = np.asarray(arr)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx])


def put_dp_local(local_arr, mesh: Mesh):
    """Build the global dp-sharded array from this process's local block.

    The global leading axis is the concatenation of every host's block in
    process order (the mesh's dp axis is laid out host-major).
    """
    local_arr = np.asarray(local_arr)
    sharding = NamedSharding(
        mesh, P(DP_AXIS, *([None] * (local_arr.ndim - 1))))
    if jax.process_count() == 1:
        return jax.device_put(local_arr, sharding)
    global_shape = (local_arr.shape[0] * jax.process_count(),
                    *local_arr.shape[1:])
    return jax.make_array_from_process_local_data(sharding, local_arr,
                                                  global_shape)


def shard_pytree(tree, spec_fn):
    """Place every leaf with its NamedSharding from spec_fn(leaf);
    process-count aware (see put_global)."""
    return jax.tree_util.tree_map(
        lambda x: put_global(x, spec_fn(x)), tree)


def sharding_tree(tree, spec_fn):
    """A pytree of NamedShardings matching ``tree`` (for jit in/out specs)."""
    return jax.tree_util.tree_map(lambda x: spec_fn(x), tree)
