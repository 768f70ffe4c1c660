"""``calibrate.py --part first`` for a cell on a mesh, with the fault that
only a feature-sharded table can have.

    python3 perfbench/calibrate_fs.py --workload <cell> --seeds 1,2,3
        [--override KEY=VALUE ...] [--faults] [--out FILE] [--cpu]

A cell under a mesh replays no pairs, so its limits hold the first three
steps' numbers alone; each seed's run ends after the third step, over the
first eight members of the cell's rows. ``--faults`` reads, beside each
seed's numbers, the faults planted in the reference put in the program's
place, against the reference as it is: ``calibrate.py``'s of the first
steps (every second row of each batch left out), and ``shard_out``: one
shard's rows left out of the gather, so that the steps read zeros where
the seed's table
has the embeddings of rows ``[capacity/fs * k, capacity/fs * (k + 1))``
(the shard that holds most of the touched rows). Not part of a benchmark
run.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def shard_out(V0, probe_rows, capacity: int, fs: int):
    """``V0`` with the rows of one key-range shard zeroed."""
    import numpy as np
    owner = np.asarray(probe_rows) // (capacity // fs)
    k = int(np.bincount(owner, minlength=fs).argmax())
    return V0 * (owner != k)[:, None]


def reading(bench: dict, workload: str, seed: int, override,
            faults: bool, require_tpu: bool) -> dict:
    from perfbench import calibrate, check, sut
    from perfbench import run as R
    loaded = R.load_cell(bench, ROOT, workload)
    config, traffic = loaded["config"], dict(loaded["traffic"])
    if require_tpu:
        sut.bind(int(loaded["cell"]["chips"]))
    drv = R.load_driver(ROOT, config)
    traffic["rows_per_epoch"] = 8 * int(config["batch_size"])
    ref_mod = R.load_reference(ROOT, config)
    cfg_kw = {k: v for k, v in config.items() if k not in R.META}
    hyper = ref_mod.Hyper.of(cfg_kw)
    capacity = int(config["hash_capacity"])
    run_root = os.path.join(ROOT, ".perfbench_run")
    os.makedirs(run_root, exist_ok=True)
    work_dir = tempfile.mkdtemp(prefix="cal_", dir=run_root)
    try:
        data = R.make_data(seed, config, traffic, work_dir, drv.N_STEPS)
        plan = drv.probe_plan(data, cfg_kw, ref_mod)
        kwargs = drv.learner_kwargs(cfg_kw, traffic, work_dir, seed,
                                    override)
        prog = drv.drive(kwargs, plan, 0.0, stop_after="first")["probe"]
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    probe_rows, batches = plan
    V0 = ref_mod.initial_V(kwargs["seed"], capacity, probe_rows, hyper)
    ref = ref_mod.follow(hyper, V0, batches)
    nums = check.numbers(prog, ref, ref_mod.rel_diff)
    planted = {}
    if faults:
        for f, (h, bs) in calibrate.first_faults(hyper, batches).items():
            planted[f] = check.numbers(ref_mod.follow(h, V0, bs), ref,
                                       ref_mod.rel_diff)
        bad = ref_mod.follow(
            hyper, shard_out(V0, probe_rows, capacity,
                             int(config.get("mesh_fs", 1))), batches)
        planted["shard_out"] = check.numbers(bad, ref, ref_mod.rel_diff)
    ok, _ = check.judge(dict(nums, epoch_rows=0.0), loaded["limits"],
                        check.names(hyper.V_dim))
    return {"seed": seed, "override": override, "correct": ok,
            "numbers": nums, "faults": planted}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--override", action="append", default=[])
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse without a TPU (no device numbers)")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    override = dict(kv.split("=", 1) for kv in args.override) or None
    for seed in (int(s) for s in args.seeds.split(",")):
        row = reading(bench, args.workload, seed, override, args.faults,
                      not args.cpu)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
