#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry point a user calls
(``difacto_tpu.__main__.main``), at the flagship model's full width: FM
V_dim=64, bf16 fused rows, a 2^21-row (1 GiB) hashed table, batch 65536,
criteo format (39 nnz/row). Seed-generated planted-model criteo text ->
``task=convert`` -> ``task=train`` for 3 epochs (epoch 0 streams and stages
the device cache, epochs 1-2 replay from it, which is also where the paired
replay program compiles) with ``model_out`` -> ``task=pred model_in=...``
over the same rows. CLI -> reader -> producer pool -> learner -> checkpoint
+ manifest -> PredictExecutor.

One process. It binds the device first and fails before generating any
data when ``jax.default_backend()`` is not ``tpu``; it exits non-zero on the
first phase that fails and nothing here downgrades a failure to a note. On
>= 4 devices it repeats the path over a sharded table (``mesh_fs=4`` and
``mesh_dp=2 mesh_fs=2`` at 2^21 rows against the one-chip trajectory,
``mesh_fs=4`` at 2^23 rows — the same 1 GiB per device, scored back on
one device) and checks the placement. Scratch data lives in a temporary directory that is removed;
the only thing written under the checkout is the compile cache
(difacto_tpu/utils/device.py).

The last stdout line is ``{"ok": true, "device": {"platform": ..., "kind":
..., "count": ...}}``.
"""

from __future__ import annotations

import glob
import importlib.metadata
import json
import logging
import math
import os
import re
import sys
import tempfile
import time

ROWS = 262144
BATCH = 65536
CAPACITY = 1 << 21
EPOCHS = 3
MODEL = ["loss=fm", "V_dim=64", "V_dtype=bfloat16", "V_threshold=0",
         "lr=0.1", "l1=1e-4", f"batch_size={BATCH}", "data_format=rec"]
# Per-epoch loss of a sharded run against the one-chip run. With f32 rows
# the mesh program reproduces one chip to 2.3e-6 on the v5e (PR 21 — inside
# the 1e-5 of tests/test_fs_sharding.py). The flagship's bf16 rows round
# every written row, and the flat and the mesh programs round differently:
# 3.5e-4 measured, next to 1.5e-4 between one chip's own bf16 and f32 runs.
# The bound is a quarter of bf16's 2^-8 spacing.
MESH_RTOL = 2.0 ** -10

_EPOCH_ROW = re.compile(r"epoch\[(\d+)\] training: Rows = (\S+), "
                        r"loss = (\S+), AUC = (\S+),")
_PRED_ROW = re.compile(r"prediction: Rows = (\S+), loss = (\S+), "
                       r"AUC = (\S+)")
_CONVERT_WORKERS = re.compile(r"\((\d+) convert workers\)")
_PRODUCERS = re.compile(r"producers: (\w+)")


def say(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class _Rows(logging.Handler):
    """The CLI's own log rows — what a user reads off a run."""

    def __init__(self) -> None:
        super().__init__(logging.INFO)
        self.lines: list = []

    def emit(self, record: logging.LogRecord) -> None:
        self.lines.append(record.getMessage())

    def take(self, pattern: re.Pattern) -> list:
        return [m.groups() for m in map(pattern.search, self.lines) if m]


class _Compiles:
    """Seconds jax spent in backend compiles (or persistent-cache
    retrievals standing in for them) and the cache's hit/write counts,
    from jax's own monitoring events — background compile threads
    included."""

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.seconds = 0.0
        self.hits = self.writes = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1


def bind() -> dict:
    """Bind the backend; fail unless it is a TPU. Nothing before this
    touches the device or the disk."""
    from difacto_tpu.utils.device import bound_device, place_compile_cache
    cache_dir = place_compile_cache()
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU: jax.default_backend() is {backend!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}). "
            "This script proves the system on the accelerator and does "
            "not fall back to the CPU.")
    dev = bound_device()
    for k, v in dev.items():
        say(k, v)
    import jaxlib
    say("jax", jax.__version__)
    say("jaxlib", jaxlib.__version__)
    say("libtpu", importlib.metadata.version("libtpu"))
    say("python", sys.version.split()[0])
    say("host_cores", os.cpu_count())
    say("compile_cache_dir", cache_dir)
    return dev


def cli(rows: _Rows, args: list) -> None:
    from difacto_tpu.__main__ import main
    say("run", "python -m difacto_tpu " + " ".join(args))
    del rows.lines[:]
    t0 = time.perf_counter()
    rc = main(args)
    say("task_seconds", round(time.perf_counter() - t0, 1))
    check(rc == 0, f"task returned {rc}: {args}")


def make_data(d: str, rows: _Rows) -> str:
    """Seed-generated planted-model criteo text -> rec2 members."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    from download import synth_criteo
    synth_criteo(d, seed=0, rows=ROWS)
    rec = f"{d}/train.rec"
    cli(rows, ["task=convert", f"data_in={d}/criteo_train.txt",
               "data_format=criteo", f"data_out={rec}",
               "data_out_format=rec", f"rec_batch_size={BATCH}"])
    workers = rows.take(_CONVERT_WORKERS)
    say("convert_workers", int(workers[0][0]) if workers else 1)
    from difacto_tpu.data.reader import expand_uri
    from difacto_tpu.data.rec import read_rec_block_ex, rec_members
    members = [read_rec_block_ex(m[0])[0].size
               for m in rec_members(*expand_uri(rec, with_sizes=True))]
    check(sum(members) == ROWS, f"convert wrote {sum(members)} rows")
    # batches never span members: this is the real fill of each
    # [batch_size, 39] step
    say("rows_per_step", f"min {min(members)} max {max(members)} "
                         f"over {len(members)} members")
    return rec


def train(rows: _Rows, rec: str, tag: str, capacity: int, mesh: list,
          model: str = "") -> list:
    """task=train for 3 epochs -> the per-epoch loss the CLI logged;
    with ``model`` also the verified checkpoint family."""
    from difacto_tpu.utils import manifest

    cli(rows, ["task=train", f"data_in={rec}", *MODEL,
               f"hash_capacity={capacity}", f"max_num_epochs={EPOCHS}",
               "stop_rel_objv=0", *mesh,
               *([f"model_out={model}"] if model else [])])
    epochs = rows.take(_EPOCH_ROW)
    check([int(e[0]) for e in epochs] == list(range(EPOCHS)),
          f"expected {EPOCHS} epoch rows, got {epochs}")
    check(all(float(e[1]) == ROWS for e in epochs),  # %g: 6 digits
          f"an epoch did not see {ROWS} rows: {epochs}")
    loss = [float(e[2]) for e in epochs]
    say(f"{tag}.loss_per_epoch", loss)
    check(all(map(math.isfinite, loss)),
          f"non-finite training loss {loss}")
    check(all(b < a for a, b in zip(loss, loss[1:])),
          f"training loss does not fall: {loss}")
    say(f"{tag}.producer_mode",
        sorted({p[0] for p in rows.take(_PRODUCERS)}))
    if model:
        parts = sorted(p for p in glob.glob(model + "_part-*")
                       if not p.endswith(".json"))
        check(bool(parts), f"no checkpoint under {model}")
        for p in parts:
            manifest.verify(p, require_manifest=True)
        say(f"{tag}.checkpoint", f"{len(parts)} file(s) verified against "
                                 "their manifests")
    return loss


def pred(rows: _Rows, rec: str, tag: str, capacity: int, model: str) -> None:
    """task=pred -> AUC re-scored from the pred file. It runs on ONE
    device, whatever layout trained the model: a mesh run's per-shard
    family loads back unsharded."""
    import numpy as np

    from difacto_tpu.losses.metrics import auc_times_n

    out_prefix = model + ".pred"
    cli(rows, ["task=pred", f"data_in={rec}", *MODEL,
               f"hash_capacity={capacity}", f"model_in={model}",
               f"pred_out={out_prefix}"])
    reported = rows.take(_PRED_ROW)
    check(len(reported) == 1 and float(reported[0][0]) == ROWS,
          f"prediction row missing or short: {reported}")
    out = np.loadtxt(out_prefix + "_part-0", dtype=np.float64)
    check(out.shape == (ROWS, 2), f"pred file shape {out.shape}")
    label, prob = out[:, 0], out[:, 1]
    check(bool(np.isfinite(prob).all()) and prob.min() >= 0
          and prob.max() <= 1, "pred probabilities not finite in [0, 1]")
    auc = auc_times_n(label, prob) / ROWS
    say(f"{tag}.pred_auc", f"{auc:.4f} (CLI reported "
                           f"{float(reported[0][2]):.4f})")
    check(auc > 0.6, f"pred AUC on the training rows {auc:.4f} <= 0.6")


def same_loss(tag: str, loss: list, ref: list) -> None:
    rel = max(abs(a / b - 1) for a, b in zip(loss, ref))
    say(f"{tag}.loss_vs_one_chip", f"max rel diff {rel:.2g} "
                                   f"(bound {MESH_RTOL:g})")
    check(rel <= MESH_RTOL,
          f"{tag} loss differs from one chip by {rel:g}")


def placement() -> None:
    """Four chips, checked FIRST (peak_bytes_in_use is a high-water
    mark): build the fs=4 store at 2^23 rows — the one-chip table's 1 GiB
    on every device — and read where it landed."""
    import jax

    from difacto_tpu.parallel import make_mesh
    from difacto_tpu.store.local import SlotStore
    from difacto_tpu.updaters.sgd_updater import SGDUpdaterParam

    param, _ = SGDUpdaterParam.init_allow_unknown(
        [tuple(a.split("=", 1)) for a in MODEL]
        + [("hash_capacity", str(CAPACITY * 4))])
    store = SlotStore(param, mesh=make_mesh(dp=1, fs=4))
    jax.block_until_ready(store.state)
    table = []
    for leaf in jax.tree_util.tree_leaves(store.state):
        shards = leaf.addressable_shards
        check(len({s.device for s in shards}) == 4,
              f"a table leaf covers {len(shards)} shards, not 4 devices")
        check(all(s.data.shape[0] == leaf.shape[0] // 4 for s in shards),
              "a table leaf's shards are not capacity/4 rows each")
    for s in store.state.VVg.addressable_shards:
        stats = s.device.memory_stats()
        table.append({"device": s.device.id, "rows": s.data.shape[0],
                      "shard_bytes": s.data.nbytes,
                      "peak_bytes_in_use": stats["peak_bytes_in_use"]})
    peaks = [t["peak_bytes_in_use"] for t in table]
    say("four_chip.placement", json.dumps(table))
    check(max(peaks) <= 1.5 * min(peaks),
          f"per-device peak after store construction is unbalanced: "
          f"{peaks}")


def main() -> int:
    dev = bind()
    compiles = _Compiles()
    rows = _Rows()
    logging.getLogger("difacto_tpu").addHandler(rows)
    from difacto_tpu import native
    say("native_parsers", str(native.get_lib() is not None).lower())

    four = dev["count"] >= 4
    if four:
        placement()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as d:
        rec = make_data(d, rows)
        one = train(rows, rec, "one_chip", CAPACITY, [], f"{d}/m1")
        pred(rows, rec, "one_chip", CAPACITY, f"{d}/m1")
        say("compile_seconds", round(compiles.seconds, 1))
        say("compile_cache", f"{compiles.hits} hits, "
                             f"{compiles.writes} writes")
        if not four:
            say("four_chip", f"not run ({dev['count']} device)")
        else:
            # same table as the one-chip run: the trajectory must match
            same_loss("fs4", train(rows, rec, "fs4", CAPACITY,
                                   ["mesh_fs=4"]), one)
            same_loss("dp2_fs2",
                      train(rows, rec, "dp2_fs2", CAPACITY,
                            ["mesh_dp=2", "mesh_fs=2"], f"{d}/m22"),
                      one)
            pred(rows, rec, "dp2_fs2", CAPACITY, f"{d}/m22")
            # the point of sharding: four times the table, the same
            # 1 GiB on every device; its 4-shard family then loads back
            # and scores on one of them (4 GiB of its 16)
            train(rows, rec, "fs4_x4", CAPACITY * 4, ["mesh_fs=4"],
                  f"{d}/m4")
            pred(rows, rec, "fs4_x4", CAPACITY * 4, f"{d}/m4")
            say("four_chip", "ok")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev["platform"],
                                 "kind": dev["device_kind"],
                                 "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
