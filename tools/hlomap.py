#!/usr/bin/env python
"""Merged static + dynamic sharding map — `make hlomap` runs this.

The static half is the sharding-flow model difacto-lint builds
(difacto_tpu/analysis/shardflow.py): every fs-scoped state program and
its layout-pin verdict, the pinning builders, and the full jit-site
universe. The dynamic half is a compiled-HLO scan
(difacto_tpu/utils/hloscan.py): per jit site, the
collectives XLA actually emitted and the memory_analysis() byte
counts, recorded either from a prior run's dump
(``DIFACTO_HLOSCAN_OUT=<path>``) or produced in-process by ``--scan``,
which drives the REAL fs-sharded train step (parallel/capacity.py) and
serve executor (serve/executor.py) on the CPU virtual mesh. Both
halves key programs by the same ``relpath:lineno`` jit-site identity
jaxtrace assigns, so merging answers:

- did ANY compiled program move the fs-sharded capacity axis whole
  across the mesh (an all-gather/all-to-all carrying the table's row
  count — ``table_hits``)?
- did any program's temp arena exceed the per-fs budget
  (``budget_hits``, DIFACTO_HLOSCAN_BUDGET)?
- was any scanned program compiled at a site the static model does not
  know (``unknown_sites`` — a shardflow discovery blind spot)?

Usage:
  python tools/hlomap.py [--scan] [--fs N] [--dynamic scan.json]
                         [--json hlomap.json] [--check]
                         [--rows N] [--budget N]

``--check`` exits 1 on any table-axis collective, budget breach, or
unknown dynamic site (CI-able; ``make ci`` runs ``--scan --fs 4
--check``); the default is informational (exit 0).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# IMPORTANT: nothing above may import jax — --scan must set the
# platform/device-count env before the first backend touch
from difacto_tpu.analysis import core  # noqa: E402
from difacto_tpu.analysis.cli import DEFAULT_PATHS  # noqa: E402
from difacto_tpu.analysis.shardflow import get_shard_model  # noqa: E402
from difacto_tpu.utils import hloscan  # noqa: E402


def drive_scan(fs: int, capacity: int, budget: int,
               tau: int = 0) -> dict:
    """Compile the fs-sharded train step AND serve executor in-process
    under DIFACTO_HLOSCAN=1 and return the scan (hloscan.programs()).

    Must be called before anything imports jax: it forces
    JAX_PLATFORMS=cpu with enough virtual host devices for the mesh —
    the same harness the tier-1 fs-sharding tests run on."""
    os.environ["DIFACTO_HLOSCAN"] = "1"
    os.environ["DIFACTO_HLOSCAN_ROWS"] = str(capacity)
    if budget:
        os.environ["DIFACTO_HLOSCAN_BUDGET"] = str(budget)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{max(8, fs)}").strip()

    import numpy as np

    # train leg: the fs-sharded fused step of parallel/capacity.py, one
    # leg at the requested fs (capacity.py scans it explicitly too)
    from difacto_tpu.parallel.capacity import (bounded_delay_report,
                                               capacity_scaling_report)
    capacity_scaling_report(fs_values=[fs], base_capacity=capacity // fs,
                            V_dim=4, batch=64, nnz_per_row=4, steps=1)

    # quantized-slot leg (ISSUE 19): the SAME fs-sharded step with the
    # int8 fused-row container — --check proves the dequant/requant
    # epilogues introduce no table-axis collective under fs sharding
    capacity_scaling_report(fs_values=[fs], base_capacity=capacity // fs,
                            V_dim=4, batch=64, nnz_per_row=4, steps=1,
                            slot_dtype="int8")

    if tau > 0:
        # bounded-delay leg: the SAME fs-sharded train step driven
        # through the real windowed pipeline (prefetch depth 2+τ) —
        # records per-τ scans under colon-free capacity.delay/* keys,
        # and --check still fails on any table-axis collective the
        # window might have introduced
        bounded_delay_report(hosts_values=(1,), taus=(tau,), fs=fs,
                             base_capacity=capacity // fs, V_dim=4,
                             batch=64, nnz_per_row=4, steps=2,
                             auc_legs=False)

    # serve leg: an fs-sharded read path through the real executor
    from difacto_tpu.data.rowblock import RowBlock
    from difacto_tpu.parallel import make_mesh
    from difacto_tpu.serve.executor import PredictExecutor
    from difacto_tpu.store.local import SlotStore
    from difacto_tpu.updaters.sgd_updater import SGDUpdaterParam

    mesh = make_mesh(dp=1, fs=fs) if fs > 1 else None
    param = SGDUpdaterParam(V_dim=4, hash_capacity=capacity,
                            V_threshold=0)
    store = SlotStore(param, mesh=mesh)
    rng = np.random.RandomState(0)
    keys = rng.randint(1, 1 << 62, 256).astype(np.uint64)
    store.push(keys, 1, np.ones(len(keys), np.float32))
    ex = PredictExecutor(store)
    nnz, batch = 4, 16
    blk = RowBlock(
        offset=np.arange(batch + 1, dtype=np.int64) * nnz,
        label=np.zeros(batch, np.float32),
        index=keys[rng.randint(0, len(keys), batch * nnz)],
        value=None)
    ex.predict(blk)
    assert ex.stats()["dispatches"] == 1
    return {"rows": capacity, "budget": budget,
            "programs": hloscan.programs()}


def build(root=".", dynamic=None) -> dict:
    """{'state_programs', 'pinning_builders', 'sites', 'programs',
    'table_hits', 'budget_hits', 'unknown_sites'} — everything the
    writers, the --check gate and the tier-1 test consume. ``dynamic``
    is a scan dict (drive_scan or hloscan.load)."""
    root = Path(root).resolve()
    paths = [p for p in DEFAULT_PATHS if (root / p).exists()]
    project = core.Project(root, paths)
    model = get_shard_model(project)
    doc = model.to_json()
    out = {
        "state_programs": doc["state_programs"],
        "pinning_builders": doc["pinning_builders"],
        "sites": doc["sites"],
        "programs": {},
        "table_hits": [],
        "budget_hits": [],
        "unknown_sites": [],
    }
    if dynamic:
        progs = dynamic["programs"]
        out["programs"] = {
            s: {"label": rec.get("label", ""),
                "table_collectives": rec.get("table_collectives", 0),
                "peak_temp_bytes": rec.get("peak_temp_bytes", 0),
                "over_budget": rec.get("over_budget", False),
                "signatures": rec.get("signatures", 0)}
            for s, rec in sorted(progs.items())}
        for v in hloscan.violations(progs):
            key = ("table_hits" if v["kind"] == "table-collective"
                   else "budget_hits")
            out[key].append(v)
        known = set(out["sites"])
        for site in sorted(progs):
            # a scan keyed by a real repo site must be a site the
            # static model discovered; non-site labels (explicit
            # record() keys) are exempt from the subset claim
            if ":" in site and site not in known:
                out["unknown_sites"].append(site)
    return out


def to_text(graph: dict) -> str:
    lines = []
    for sid, rec in sorted(graph["state_programs"].items()):
        mark = "PIN  " if rec["pinned"] else "LOOSE"
        lines.append(f"{mark} {sid}  jit({rec['target']}) "
                     f"pin={rec['pin']} donate={rec['donate_argnums']}")
    lines.append(f"pinning builders: "
                 f"{', '.join(graph['pinning_builders']) or '-'}")
    for site, rec in sorted(graph["programs"].items()):
        lines.append(
            f"scan {site}  {rec['label']}  "
            f"table_collectives={rec['table_collectives']} "
            f"peak_temp_bytes={rec['peak_temp_bytes']}"
            f"{'  OVER-BUDGET' if rec['over_budget'] else ''}")
    for key in ("table_hits", "budget_hits"):
        for v in graph[key]:
            lines.append(f"{key.upper().replace('_', '-')}: "
                         f"{v['site']}  {v['detail']}")
    for site in graph["unknown_sites"]:
        lines.append(f"UNKNOWN-SITES: {site}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="merged static+dynamic sharding map "
                    "(docs/static_analysis.md v5)")
    ap.add_argument("--root", default=".")
    ap.add_argument("--scan", action="store_true",
                    help="compile the fs train step + serve executor "
                         "in-process and scan their HLO (sets "
                         "JAX_PLATFORMS/XLA_FLAGS; do not import jax "
                         "before this)")
    ap.add_argument("--fs", type=int, default=4,
                    help="fs degree for --scan (default 4)")
    ap.add_argument("--tau", type=int, default=0,
                    help="bounded-delay window for an extra --scan leg "
                         "driving the windowed fs train step "
                         "(0 = skip)")
    ap.add_argument("--rows", type=int, default=4096,
                    help="table capacity for --scan legs (divisible "
                         "by fs; default 4096)")
    ap.add_argument("--budget", type=int,
                    default=256 * 1024 * 1024,
                    help="peak temp-arena budget in bytes for --scan "
                         "(default 256MiB; 0 disables)")
    ap.add_argument("--dynamic", default=None,
                    help="hloscan dump (DIFACTO_HLOSCAN_OUT) to merge")
    ap.add_argument("--json", default=None, help="write JSON here")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 on any table-axis collective, budget "
                         "breach, or dynamic site outside the static "
                         "model")
    args = ap.parse_args(argv)
    dynamic = None
    if args.scan:
        dynamic = drive_scan(args.fs, args.rows, args.budget, args.tau)
    elif args.dynamic:
        dynamic = hloscan.load(args.dynamic)
    graph = build(args.root, dynamic)
    if args.json:
        Path(args.json).write_text(
            json.dumps(graph, indent=1, sort_keys=True) + "\n",
            encoding="utf-8")
        print(f"hlomap: wrote {args.json}")
    print(to_text(graph))
    if args.check and (graph["table_hits"] or graph["budget_hits"]
                       or graph["unknown_sites"]):
        print("hlomap: CHECK FAILED — table-axis collective, temp "
              "budget breach, or scan site outside the static model",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
