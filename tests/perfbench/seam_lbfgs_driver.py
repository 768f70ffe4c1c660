"""A driver of the L-BFGS learner, for the CPU test of the seam between the
harness and the learner a configuration names (``test_perfbench_seam.py``
copies it into a tiny benchmark and names it in a configuration's
``"driver"``). Not a benchmark driver and not a test file.

An epoch's rows are the examples it passed over: every function
evaluation is one pass over the rows, the first evaluation and each trial
of the line search alike. The window is ready once the two-loop's history
is full (``m`` epochs), so no program it runs has a new shape after. The
probe keeps the objective the learner reports after epoch 1 and the
weights it reports it at; ``compare`` sets it against the plain
objective at those weights (``seam_lbfgs_reference.py``), and
``reading`` (``calibrate.py``) does the same with no window, beside the
faults it plants in that objective.
"""

from __future__ import annotations

import functools
import math
import time

import numpy as np

from perfbench import check, sut

LEARNER = "lbfgs"
N_STEPS = 1 << 20       # every member: the objective is over all rows
PROBE_EPOCH = 1
HUGE_EPOCHS = 1_000_000
# planted in the reference put in the program's place (``reading``): the
# objective with half of its rows' loss, and without its l2 term
FAULTS = ("half_the_loss", "no_regulariser")
# the history of the two-loop cut to 5 pairs, members of 64 rows
TINY = {"m": 5, "batch_size": 64}


class WindowClosed(Exception):
    """Raised out of the learner's run at the mark that closes the
    window: its epoch loop takes no new bound once it runs."""


def learner_kwargs(config: dict, traffic: dict, data_dir: str, seed: int,
                   override: dict = None) -> dict:
    # the members' rows are the traffic's; the learner reads its data in
    # chunks of bytes and takes no batch size
    kw = {k: v for k, v in config.items() if k != "batch_size"}
    kw.update(traffic.get("learner", {}))
    kw.update(override or {})
    kw.update(data_in=data_dir, max_num_epochs=HUGE_EPOCHS,
              min_num_epochs=HUGE_EPOCHS, stop_rel_objv=0,
              seed=int(seed) % (1 << 31))
    return kw


def probe_plan(data: dict, config: dict, ref_mod) -> dict:
    """Every row as the reference takes it: its features' reversed ids
    (the ids the rec members hold) and its label."""
    kept = [data["kept"][m] for m in range(data["n_members"])]
    return {"rev": np.concatenate([data["tables"].rev_of(g)
                                   for _, g in kept]),
            "y": np.concatenate([y for y, _ in kept])}


def step_work(data: dict, config: dict) -> dict:
    """One pass over ``batch_size`` rows: each non-zero's index read and
    its weight gathered and its gradient added (2 flops), each row's
    label read and its loss taken (about 5), each distinct feature's
    weight read and its gradient written once."""
    rows = data["batch"]
    nnz = rows * data["width"]
    u = data["uniq_per_step"]
    return {"bytes": float(4 * nnz + 4 * rows + 8 * u),
            "flops": float(2 * nnz + 5 * rows)}


def names(config: dict) -> tuple:
    return ("objv", "epoch_rows")


def drive(kwargs: dict, plan, seconds: float, trace_dir: str = None,
          stop_after: str = "") -> dict:
    """``stop_after`` "probe" ends the run at the probe's epoch, with the
    probe alone: ``reading`` needs no window."""
    from difacto_tpu.learners import Learner
    learner = Learner.create(LEARNER)
    t_init = time.perf_counter()
    remain = learner.init([(k, str(v)) for k, v in kwargs.items()])
    if remain:
        raise ValueError(f"keys the learner does not know: {remain}")
    if learner.k != 0:
        raise ValueError("the seam's probe reads a model of w alone")
    init_s = time.perf_counter() - t_init

    passes = [0, 0]     # function evaluations: all, and at the last mark
    calc_grad = learner._calc_grad

    def counted(weights):
        passes[0] += 1
        return calc_grad(weights)

    learner._calc_grad = counted
    win = sut.Window(seconds, trace_dir, stages=dict,
                     ready=lambda: win.marks[-1][0] >= learner.uparam.m)
    probe = {}

    def on_epoch_end(k, prog):
        rows = (passes[0] - passes[1]) * learner.ntrain
        passes[1] = passes[0]
        if k == PROBE_EPOCH:
            probe.update(
                objv=float(prog.objv),
                feaids=np.asarray(learner.feaids),
                w=np.asarray(learner.weights)[learner.offsets[:-1]])
            if stop_after == "probe":
                raise WindowClosed()
        if win.mark(k, rows):
            raise WindowClosed()

    learner.add_epoch_end_callback(on_epoch_end)
    t0 = time.perf_counter()
    try:
        learner.run()
    except WindowClosed:
        pass
    if stop_after == "probe":
        learner.epoch_end_callbacks.clear()
        return {"probe": probe}
    out = dict(win.summary(t0), init_s=init_s, producer_mode=None,
               paired_dispatches=None, device_cache=None,
               table_rows=int(learner.N_pad),
               table_bytes=int(learner.weights.nbytes),
               probe=probe or None,
               memory_peak_bytes=sut.memory_peak_bytes())
    learner.epoch_end_callbacks.clear()
    return out


def compare(ref_mod, config: dict, kwargs: dict, plan, res: dict,
            data: dict) -> dict:
    """``objv``: the gap between the objective the learner reported after
    epoch 1 and the plain one at its weights; ``epoch_rows``: the largest
    share of the rows by which an epoch of the window missed a whole
    number of passes."""
    p = res["probe"]
    ref = ref_mod.objective(plan["rev"], plan["y"], p["feaids"], p["w"],
                            float(config["l2"]))
    by_epoch = res["window_rows_by_epoch"]
    nums = {"objv": check.gap(p["objv"], ref),
            "epoch_rows": max((math.fmod(r, data["rows"]) / data["rows"]
                               for r in by_epoch), default=math.inf)}
    return {"numbers": nums, "names": names(config),
            "said": {"program": {"objv": p["objv"]},
                     "reference": {"objv": ref}}}


def reading(ref_mod, config: dict, traffic: dict, limits: dict,
            work_dir: str, seed: int, override: dict = None,
            part: str = None, faults: bool = False) -> dict:
    """``calibrate.py``'s one seed: the run up to the probe's epoch (the
    one part, "probe"), its ``objv`` against the plain objective, and
    with ``faults`` that of each of ``FAULTS`` at the same weights."""
    from perfbench import run as R
    if part not in (None, "probe"):
        raise SystemExit(f"perfbench: no part {part!r}; the seam's "
                         "driver reads \"probe\"")
    data = R.make_data(seed, config, traffic, work_dir, N_STEPS)
    plan = probe_plan(data, config, ref_mod)
    kwargs = learner_kwargs(config, traffic, work_dir, seed, override)
    p = drive(kwargs, plan, 0.0, stop_after="probe")["probe"]
    objv = functools.partial(ref_mod.objective, plan["rev"], plan["y"],
                             p["feaids"], p["w"])
    ref, loss = objv(float(config["l2"])), objv(0.0)
    nums = {"objv": check.gap(p["objv"], ref)}
    planted = {}
    if faults:
        planted = {"half_the_loss": 0.5 * loss + (ref - loss),
                   "no_regulariser": loss}
        planted = {f: {"objv": check.gap(v, ref)}
                   for f, v in planted.items()}
    ok, _ = check.judge(dict(nums, epoch_rows=0.0), limits, names(config))
    return {"part": "probe", "correct": ok, "numbers": nums,
            "faults": planted, "program": {"objv": p["objv"]},
            "reference": {"objv": ref}}
