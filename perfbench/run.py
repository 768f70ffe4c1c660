"""The benchmark's entry.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

One cell of ``BENCHMARK.json`` a run: makes the rows and the table from
the seed, drives the program's training entry in this process through
the configuration's driver, measures from the first steady epoch mark
for ``--seconds``, compares what the driver's probe read (for the SGD
learner: its first steps and one call of the program that the window
times) with the plain reference, and prints one JSON object as the last
line of standard output. Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.

Driven by data: a cell names a configuration (``BENCHMARK.json`` gives
its file) and a traffic mix (``traffic/<name>.json``); its limits are in
``limits/<cell>.json``; each per-layer metric is read by
``metrics/<name>.py``. Nothing here names a cell or a learner.

A configuration's ``"driver"`` names, by path from the root, the module
that trains its learner; without the key it is ``perfbench/sut.py``, the
SGD learner. A driver owns what is particular to its learner (``config``
below is the configuration's keys, those of ``META`` left out):

- ``LEARNER``: the name the program registers the learner under;
- ``N_STEPS``: the members of the traffic whose rows ``make_data`` keeps
  for the comparison;
- ``learner_kwargs(config, traffic, data_dir, seed, override)``: the keys
  the learner is built with;
- ``probe_plan(data, config, ref_mod)``: what the run watches and the
  reference follows;
- ``drive(kwargs, plan, seconds, trace_dir)``: builds the learner, runs it,
  keeps the window between two epoch-end marks and returns its marks,
  the run's summary, the probe's readings and ``memory_peak_bytes``
  (``sut.drive`` lists the keys; a key the learner has no value for is
  ``None``);
- ``step_work(data, config)``: the necessary bytes and flops of one step
  of ``batch_size`` rows (``work.py``);
- ``compare(ref_mod, config, kwargs, plan, res, data)``: the numbers
  compared, the names that must be among them, and what the
  ``reference`` line prints.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

META = ("precision", "reference", "control", "about", "driver")


def say(key: str, value) -> None:
    """An earlier line of standard output: ``key: value``."""
    print(f"{key}: {value}", flush=True)


def load_cell(bench: dict, root: str, workload: str) -> dict:
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"perfbench: no workload {workload!r} in "
                         "BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    bdir = os.path.join(root, bench["paths"][0])
    with open(os.path.join(bdir, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    lim_path = os.path.join(bdir, "limits", workload + ".json")
    limits = {}
    if os.path.exists(lim_path):
        with open(lim_path) as f:
            limits = json.load(f)
    return {"cell": cell, "config": config, "traffic": traffic,
            "limits": limits, "bdir": bdir}


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod      # a dataclass looks its module up there
    spec.loader.exec_module(mod)
    return mod


def load_reference(root: str, config: dict):
    """The configuration's plain reference, by the path its file gives;
    its numbers come from the configuration (``Hyper.of``)."""
    path = os.path.join(root, config["reference"])
    return _load_module(
        path, "perfbench_reference_" + os.path.basename(path)[:-3])


def load_driver(root: str, config: dict):
    """The module that trains the configuration's learner: the file its
    ``"driver"`` names, or ``perfbench.sut`` where it names none. A path
    that is no file under the root, or a learner the program does not
    register, ends the run here, before any data is made."""
    from perfbench import sut
    path = config.get("driver")
    if path is None:
        drv = sut
    else:
        full = os.path.normpath(os.path.join(root, path))
        if os.path.isabs(path) or not full.startswith(
                os.path.join(os.path.normpath(root), "")) \
                or not full.endswith(".py") or not os.path.isfile(full):
            raise SystemExit(f"perfbench: the configuration's \"driver\" "
                             f"{path!r} is no .py file under {root}")
        drv = _load_module(full, "perfbench_driver_"
                           + os.path.basename(full)[:-3])
    name = getattr(drv, "LEARNER", None)
    if not isinstance(name, str) or not sut.learner_known(name):
        raise SystemExit(f"perfbench: the configuration's \"driver\" "
                         f"{path or 'perfbench/sut.py'} trains the learner "
                         f"{name!r}, which the program does not register")
    return drv


def load_reader(bdir: str, metric: str):
    path = os.path.join(bdir, "metrics", metric + ".py")
    if not os.path.exists(path):
        return None
    return _load_module(
        path, "perfbench_metric_" + metric.replace(".", "_")).read


def metrics_of(bench: dict, kind: str, workload: str) -> list:
    """The metrics of ``kind`` that this cell reports: those without a
    ``workloads`` key whose end-to-end metric the cell reports, and those
    that list the cell."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def make_data(seed: int, config: dict, traffic: dict, data_dir: str,
              n_probe: int) -> dict:
    """Rows from the seed, written as rec members; keeps the first
    ``n_probe`` members' rows for the comparison."""
    from perfbench import gen, sut
    spec = gen.Spec(**traffic["generator"])
    batch = int(config["batch_size"])
    rows = int(traffic["rows_per_epoch"])
    if rows % batch:
        raise ValueError("rows_per_epoch is a multiple of batch_size: "
                         "every step is full")
    n_members = rows // batch
    tables = gen.make_tables(seed, spec)
    kept, uniq_n, nbytes = {}, [0] * n_members, [0] * n_members

    def sink(m, label, g, uniq, index):
        nbytes[m] = sut.write_member(data_dir, m, label, uniq, index,
                                     spec.width)
        uniq_n[m] = len(uniq)
        if m < n_probe:
            kept[m] = (label, g)

    gen.members(seed, n_members, batch, tables, sink)
    return {"tables": tables, "kept": kept, "n_members": n_members,
            "rows": rows, "batch": batch, "width": spec.width,
            "uniq_per_step": sum(uniq_n) / n_members,
            "bytes_written": sum(nbytes)}


def run_cell(bench: dict, root: str, workload: str, seed: int,
             seconds: float, trace: bool, require_tpu: bool = True,
             override: dict = None, out=say) -> dict:
    """One run of one cell -> the result object (the last line)."""
    from perfbench import check, sut, tracered, work

    loaded = load_cell(bench, root, workload)
    cell, config, traffic = (loaded["cell"], loaded["config"],
                             loaded["traffic"])
    chips = int(cell["chips"])
    if require_tpu:
        dev = sut.bind(chips)
    else:
        dev = sut.describe()
    bound_s = time.perf_counter() - T_START
    compiles = sut.Compiles()
    drv = load_driver(root, config)
    import jax
    import jaxlib
    out("workload", workload)
    out("device", json.dumps(dev))
    out("versions", f"jax {jax.__version__} jaxlib {jaxlib.__version__}")
    if override:
        out("override", json.dumps(override))

    run_root = os.path.join(root, ".perfbench_run")
    os.makedirs(run_root, exist_ok=True)
    work_dir = tempfile.mkdtemp(prefix="run_", dir=run_root)
    try:
        data_dir = os.path.join(work_dir, "data.rec")
        os.makedirs(data_dir)
        t0 = time.perf_counter()
        data = make_data(seed, config, traffic, data_dir, drv.N_STEPS)
        data_s = round(time.perf_counter() - t0, 3)
        out("data", json.dumps({
            "rows_per_epoch": data["rows"],
            "steps_per_epoch": data["n_members"],
            "uniq_features_per_step": round(data["uniq_per_step"], 1),
            "bytes_written": data["bytes_written"],
            "seconds": data_s}))

        ref_mod = load_reference(root, config)
        cfg_kw = {k: v for k, v in config.items() if k not in META}
        plan = drv.probe_plan(data, cfg_kw, ref_mod)
        kwargs = drv.learner_kwargs(cfg_kw, traffic, data_dir, seed,
                                    override)
        window = (min(seconds, float(traffic.get("trace_seconds",
                                                 seconds)))
                  if trace else seconds)
        trace_dir = os.path.join(work_dir, "trace") if trace else None
        res = drv.drive(kwargs, plan, window, trace_dir)
        setup_s = res["t_open"] - T_START
        compile_s = compiles.seconds
    finally:
        shutil.rmtree(os.path.join(work_dir, "data.rec"),
                      ignore_errors=True)

    try:
        steps = res["window_rows"] / data["batch"]
        out("window", json.dumps({
            "seconds": res["window_s"], "rows": res["window_rows"],
            "epochs": res["window_epochs"], "steps": steps,
            "warm_epochs": res["warm_epochs"],
            "epoch0_s": round(res["epoch0_s"], 3),
            "producer_mode": res["producer_mode"],
            "paired_dispatches": res["paired_dispatches"],
            "device_cache": res["device_cache"],
            "stages_s": {k: round(v, 4)
                         for k, v in res["stages"].items()},
            "table_rows": res["table_rows"],
            "table_bytes": res["table_bytes"]}))
        # where set-up went: start to device bound, the seed's rows,
        # table init with the probe, epoch 0, the warm epochs
        out("setup", json.dumps({
            "bound_s": round(bound_s, 3), "data_s": data_s,
            "init_s": round(res["init_s"], 3),
            "epoch0_s": round(res["epoch0_s"], 3),
            "warm_s": round(res["warm_s"], 3),
            "setup_s": round(setup_s, 3)}))
        out("compile", json.dumps({
            "seconds_to_window": round(compile_s, 3),
            "count": compiles.count, "cache_hits": compiles.hits,
            "cache_misses": compiles.misses}))

        rate = res["window_rows"] / res["window_s"]
        values = {"setup_s": (setup_s, "s")}
        for m in metrics_of(bench, "end_to_end", workload):
            if m["name"] != "setup_s":
                values[m["name"]] = (rate, m["unit"])

        device = {"platform": dev["platform"], "kind": dev["kind"],
                  "count": dev["count"],
                  "memory_peak_bytes": res["memory_peak_bytes"]}
        result = {"correct": False, "attempted": int(round(steps)),
                  "failed": 0, "metrics": {}, "device": device}

        if trace:
            rows = tracered.load_events(tracered.find_xplane(trace_dir))
            red = tracered.reduce(rows, res["window_s"])
            w = drv.step_work(data, cfg_kw)
            peaks = (work.load_peaks(dev["kind"]) if require_tpu
                     else None)
            least = (work.least_seconds(w, peaks, chips) if peaks
                     else None)
            out("work", json.dumps({"step": w, "least": least}))
            out("trace", json.dumps({
                k: red.get(k) for k in ("devices", "busy_s",
                                        "busy_s_fullest", "window_s",
                                        "clipped", "busy_s_unclipped",
                                        "modules")}))
            ctx = {"res": res, "trace": red, "least": least,
                   "steps": steps, "compile_s": compile_s,
                   "chips": chips, "cell": workload, "config": cfg_kw,
                   "data": {k: data[k] for k in (
                       "rows", "batch", "width", "n_members",
                       "uniq_per_step")}}
            for m in metrics_of(bench, "per_layer", workload):
                read = load_reader(loaded["bdir"], m["name"])
                v = read(ctx) if read is not None else None
                if v is not None and math.isfinite(v):
                    result["metrics"][m["name"]] = {"value": v,
                                                    "unit": m["unit"]}
            device["busy_s"] = red.get("busy_s", 0.0)
            device["window_s"] = red.get("window_s", res["window_s"])
            result["breakdown"] = {
                "device_ops": red.get("device_ops", []),
                "idle_gaps": red.get("idle_gaps", [])}
        else:
            result["metrics"] = {k: {"value": v, "unit": u}
                                 for k, (v, u) in values.items()}

        # the comparison, once the window has closed and the program's
        # state is freed; not counted in setup_s
        t0 = time.perf_counter()
        compared = drv.compare(ref_mod, cfg_kw, kwargs, plan, res, data)
        ok, checked = check.judge(compared["numbers"], loaded["limits"],
                                  compared["names"])
        out("reference", json.dumps(dict(
            seconds=round(time.perf_counter() - t0, 3),
            **compared["said"])))
        result["correct"] = bool(ok)
        result["checked"] = checked
        return result
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0)
    # the lower-precision control and fault readings; the driver's runs
    # never pass it
    ap.add_argument("--override", action="append", default=[],
                    metavar="KEY=VALUE")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    override = dict(kv.split("=", 1) for kv in args.override)
    result = run_cell(bench, ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), override=override or None)
    for name, c in result["checked"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
