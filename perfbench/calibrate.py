"""Read the comparison's numbers over many seeds in one process.

    python3 perfbench/calibrate.py --workload <cell> --seeds 1,2,3
        [--part <part>] [--override KEY=VALUE ...] [--faults]
        [--out FILE] [--cpu]

The tool that the limits in ``limits/`` were set with. For each seed it
has the cell's driver (``run.load_driver``) read the run's numbers with
no measured window, through the driver's ``reading``: the program as the
configuration states it, or with ``--override`` its lower-precision
control; ``--part`` names what of the run the driver reads (its own
parts, its first by default), and ``--faults`` asks for the faults it
plants in the reference put in the program's place, against the
reference as it is, beside each seed's numbers. A configuration whose
program has no lower precision to run as its control names one of its
driver's ``FAULTS`` instead: ``"control": {"fault": "<name>", "why":
...}``. It prints each seed's row and, at the end, the largest and the
smallest of each number. A driver without ``reading`` ends the tool
before any data is made. Not part of a benchmark run. (The SGD driver's
parts and faults: ``sut.reading``.)
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


def reading(bench: dict, workload: str, seed: int, override, part,
            faults: bool, require_tpu: bool) -> dict:
    """One seed's numbers, and with ``faults`` the planted faults'."""
    from perfbench import sut
    from perfbench import run as R
    loaded = R.load_cell(bench, ROOT, workload)
    config = loaded["config"]
    drv = R.load_driver(ROOT, config)
    if not callable(getattr(drv, "reading", None)):
        path = config.get("driver", "perfbench/sut.py")
        raise SystemExit(
            f"perfbench: the driver {path} has no reading(ref_mod, config, "
            "traffic, limits, work_dir, seed, override, part, faults), "
            "through which calibrate.py reads a seed")
    if require_tpu:
        sut.bind(int(loaded["cell"]["chips"]))
    ref_mod = R.load_reference(ROOT, config)
    cfg_kw = {k: v for k, v in config.items() if k not in R.META}
    run_root = os.path.join(ROOT, ".perfbench_run")
    os.makedirs(run_root, exist_ok=True)
    work_dir = tempfile.mkdtemp(prefix="cal_", dir=run_root)
    try:
        got = drv.reading(ref_mod, cfg_kw, loaded["traffic"],
                          loaded["limits"], work_dir, seed, override, part,
                          faults)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    return dict({"seed": seed, "override": override}, **got)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--part", default=None,
                    help="what of the run the driver reads (its parts)")
    ap.add_argument("--override", action="append", default=[])
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse without a TPU (no device numbers)")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    override = dict(kv.split("=", 1) for kv in args.override) or None
    table = []
    for seed in (int(s) for s in args.seeds.split(",")):
        row = reading(bench, args.workload, seed, override, args.part,
                      args.faults, not args.cpu)
        table.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")

    def span(rows):
        out = {}
        for r in rows:
            for name, v in r.items():
                lo, hi = out.get(name, (float(v), float(v)))
                out[name] = (min(lo, float(v)), max(hi, float(v)))
        return {k: {"min": lo, "max": hi} for k, (lo, hi) in out.items()}

    summary = {"workload": args.workload, "override": override,
               "part": table[0]["part"], "seeds": len(table),
               "numbers": span(r["numbers"] for r in table)}
    for f in sorted({f for r in table for f in r["faults"]}):
        summary["fault " + f] = span(r["faults"][f] for r in table
                                     if f in r["faults"])
    print("summary", json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
