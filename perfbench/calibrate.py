"""Read the comparison's numbers over many seeds in one process.

    python3 perfbench/calibrate.py --workload <cell> --seeds 1,2,3
        [--part first|pair] [--override KEY=VALUE ...] [--faults]
        [--out FILE]

The tool that the limits in ``limits/`` were set with. For each seed it
drives the cell's learner (the program as the configuration states it, or
with ``--override`` its lower-precision control) and prints the run's
numbers; at the end the largest and the smallest of each. Training's
readings need no measured window. ``--part pair`` (the default) runs the
cell's own epoch 0 and ends after the first call of the pair-replay
executable in the epoch that follows; ``--part first`` ends after the
third step, over the first eight members of the cell's rows, and reads
the first steps' numbers alone (enough for a control that they fail).
``--faults`` reads, beside each seed's numbers, the faults planted in the
reference put in the program's place, against the reference as it is
(``FAULTS``): every second row of each batch left out (both parts), a
second step of the pair that reads the state from before the first, and,
where the table is flat (``V_dim = 0``, l1 logistic regression), the soft
threshold left out, so that no w is exactly 0. A configuration whose
program has no lower precision to run as its control names one of them
instead: ``"control": {"fault": "<name>", "why": ...}``. Not part of a
benchmark run.
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

PAIR_FAULTS = ("stale", "half_batch")
FAULTS = PAIR_FAULTS + ("no_l1",)


def first_faults(hyper, batches) -> dict:
    """{fault: (hyper, batches)} of the first steps' planted faults that
    a table of this layout can show: what the reference follows in the
    program's place."""
    import dataclasses
    out = {"half_batch": (hyper, [(i[::2], y[::2]) for i, y in batches])}
    if hyper.V_dim == 0:
        # w = z / eta wherever z is not 0: only l1 makes a weight that a
        # batch touched exactly 0
        out["no_l1"] = (dataclasses.replace(hyper, l1=0.0), batches)
    return out


def reading(bench: dict, workload: str, seed: int, override, part: str,
            faults: bool, require_tpu: bool) -> dict:
    """One seed's numbers, and with ``faults`` the planted faults'."""
    from perfbench import check, sut
    from perfbench import run as R
    loaded = R.load_cell(bench, ROOT, workload)
    config, traffic = loaded["config"], dict(loaded["traffic"])
    if require_tpu:
        sut.bind(int(loaded["cell"]["chips"]))
    drv = R.load_driver(ROOT, config)
    if part == "first":
        traffic["rows_per_epoch"] = 8 * int(config["batch_size"])
    ref_mod = R.load_reference(ROOT, config)
    cfg_kw = {k: v for k, v in config.items() if k not in R.META}
    hyper = ref_mod.Hyper.of(cfg_kw)
    run_root = os.path.join(ROOT, ".perfbench_run")
    os.makedirs(run_root, exist_ok=True)
    work_dir = tempfile.mkdtemp(prefix="cal_", dir=run_root)
    try:
        data = R.make_data(seed, config, traffic, work_dir, drv.N_STEPS)
        plan = drv.probe_plan(data, cfg_kw, ref_mod)
        kwargs = drv.learner_kwargs(cfg_kw, traffic, work_dir, seed,
                                    override)
        prog = drv.drive(kwargs, plan, 0.0, stop_after=part)["probe"]
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    probe_rows, batches = plan
    V0 = ref_mod.initial_V(kwargs["seed"], int(config["hash_capacity"]),
                           probe_rows, hyper)
    ref = ref_mod.follow(hyper, V0, batches)
    nums = check.numbers(prog, ref, ref_mod.rel_diff)
    planted = {}
    if faults:
        for f, (h, bs) in first_faults(hyper, batches).items():
            planted[f] = check.numbers(ref_mod.follow(h, V0, bs), ref,
                                       ref_mod.rel_diff)
    pair = prog.pop("pair")
    if pair is not None:
        pref = ref_mod.follow_pair(hyper, pair["before"], batches[:2])
        nums.update(ref_mod.pair_numbers(pair, pref, check.gap))
        for f in PAIR_FAULTS if faults else ():
            bad = ref_mod.follow_pair(hyper, pair["before"], batches[:2],
                                      fault=f)
            planted.setdefault(f, {}).update(ref_mod.pair_numbers(
                dict(bad, before=pair["before"]), pref, check.gap))
    ok, _ = check.judge(dict(nums, epoch_rows=0.0), loaded["limits"],
                        check.names(hyper.V_dim))
    for side in (prog, ref):
        side.pop("rows")
    return {"seed": seed, "override": override, "part": part,
            "correct": ok, "numbers": nums, "faults": planted,
            "program": prog, "reference": ref}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--part", default="pair", choices=["first", "pair"])
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
               "part": args.part, "seeds": len(table),
               "numbers": span(r["numbers"] for r in table)}
    for f in sorted({f for r in table for f in r["faults"]}):
        summary["fault " + f] = span(r["faults"][f] for r in table
                                     if f in r["faults"])
    print("summary", json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
