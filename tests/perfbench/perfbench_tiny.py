"""A tiny copy of the benchmark for the CPU tests: the harness's own files
with the table, the batch and the epoch cut down, in a directory of the
test's. Not a test file."""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# a cell on a mesh replays no pairs: the first steps' numbers alone
FIRST_LIMITS = dict({n: 1e-5 for n in (
    "loss1", "loss2", "loss3", "grad_w", "grad_V", "change_w", "change_V",
    "keep_V", "round_V", "round_Vg")}, epoch_rows=0)
TINY_LIMITS = dict(FIRST_LIMITS, **{n: 1e-5 for n in (
    "pair_loss1", "pair_loss2", "pair_change_w", "pair_change_V",
    "pair_round_V")})


# the flat table (V_dim = 0): no number of V
FLAT_LIMITS = dict({n: 1e-5 for n in (
    "loss1", "loss2", "loss3", "grad_w", "change_w", "round_w", "round_z",
    "round_sg", "zero_w", "pair_loss1", "pair_loss2", "pair_change_w",
    "pair_round_w")}, epoch_rows=0)
# l1 logistic regression on the flagship's replay cell; an l1 that zeroes
# some of the weights a tiny batch touches (the cell's 1e-4 zeroes none)
FLAT = dict(V_dim=0, l1=0.3, limits=FLAT_LIMITS)


def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# the traffics' learner keys that the SGD learner alone takes: a traffic
# file whose learner block carries one is cut for that learner too
SGD_TRAFFIC_KEYS = ("device_cache_mb", "shuffle", "report_interval",
                    "num_jobs_per_epoch")


def cut_config(root: str, config: str, batch: int = None,
               capacity: int = None, **cfg) -> dict:
    """Cut ``root``'s configuration ``config`` to the tiny size that its
    driver gives (``TINY``; ``run.load_driver`` finds the driver under
    ``root``), then to ``batch`` rows a step, a ``capacity``-row table
    and the keys of ``cfg``; returns it as written."""
    from perfbench import run as R
    path = os.path.join(root, "perfbench", "configs", config + ".json")
    with open(path) as f:
        c = json.load(f)
    c.update(getattr(R.load_driver(root, c), "TINY", {}))
    if batch is not None:
        c["batch_size"] = batch
    if capacity is not None:
        c["hash_capacity"] = capacity
    c.update(cfg)
    with open(path, "w") as f:
        json.dump(c, f)
    return c


def make_root(tmp: str, config: str = "fm_v64_criteo", batch: int = None,
              steps: int = 8, capacity: int = None, limits=None,
              **cfg) -> str:
    """``tmp/perfbench`` = the benchmark's files, with ``config`` cut as
    ``cut_config`` cuts it (for the SGD driver: a 4096-row float32 table,
    batches of 64 rows) and every traffic mix to ``steps`` steps of its
    batch over a few hundred tokens."""
    bdir = os.path.join(tmp, "perfbench")
    shutil.copytree(os.path.join(ROOT, "perfbench"), bdir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    c = cut_config(tmp, config, batch, capacity, **cfg)
    for name in os.listdir(os.path.join(bdir, "traffic")):
        tpath = os.path.join(bdir, "traffic", name)
        with open(tpath) as f:
            t = json.load(f)
        t["rows_per_epoch"] = int(c["batch_size"]) * steps
        t["generator"].update(int_tokens=20, cat_tokens=300)
        t["trace_seconds"] = 0.3
        learner = t["learner"]
        if set(learner) & set(SGD_TRAFFIC_KEYS):
            learner["producer_mode"] = "thread"
            if learner.get("device_cache_mb"):
                learner["device_cache_mb"] = 64
        with open(tpath, "w") as f:
            json.dump(t, f)
    os.makedirs(os.path.join(bdir, "limits"), exist_ok=True)
    for w in bench()["workloads"]:
        with open(os.path.join(bdir, "limits", w["name"] + ".json"),
                  "w") as f:
            json.dump(limits or TINY_LIMITS, f)
    return tmp


def run(tmp: str, workload: str = "fm_v64_criteo.replay", seed: int = 5,
        seconds: float = 0.0, trace: bool = False, override=None):
    from perfbench import run as R
    lines = {}
    res = R.run_cell(bench(), tmp, workload, seed, seconds, trace,
                     require_tpu=False, override=override,
                     out=lambda k, v: lines.__setitem__(k, v))
    return res, lines
