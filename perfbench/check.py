"""The comparison that decides ``correct``.

Each number is a gap between what the program's first steps produced and
what the plain reference gives for the same rows from the same seed, and
each has a limit of its own, from ``limits/<workload>.json``:

- ``loss1..3``: |program - reference| / reference, the loss of each step;
- ``grad_w``, ``grad_V``: the gap between the norms of a leaf's first
  gradient as the optimizer got it, over the reference's norm;
- ``change_w``, ``change_V``: the same for the leaf's change after the
  last step;
- ``keep_V``: over the touched rows whose embedding the reference never
  updated, the norm of the difference between V as the table holds it
  after the last step and the reference's (the seed's table in the
  configuration's storage type), over the reference's norm;
- ``round_V``, ``round_Vg``: the same over the rows that the reference
  did update, for V and for AdaGrad's Vg.

- ``pair_loss1..2``, ``pair_change_w``, ``pair_change_V``,
  ``pair_round_V`` (cells whose window replays in
  pairs): the same kinds of number for one call of the pair-replay
  executable, the program that the window times, against the
  reference's two steps from the rows as they stood before the call;
- ``epoch_rows``: the largest gap between the rows an epoch of the window
  reports and the rows of the traffic's epoch; exact, limit 0.

A cell's limits file names the numbers it compares: the ten of the first
steps always, the pair's where the file lists them. A number that the
run produced and the file does not limit, or the reverse, fails.

The first seven are gaps of norms, not norms of a difference: rounding
that is not biased all but cancels in them, a wrong step does not. That
is also why they cannot tell 8-bit rows from bfloat16 rows, whose
rounding is unbiased (PERF.md, section 2): the last three, row by row,
are what a lower storage precision fails.
"""

from __future__ import annotations

import math

NUMBERS = ("loss1", "loss2", "loss3", "grad_w", "grad_V", "change_w",
           "change_V", "keep_V", "round_V", "round_Vg")


def gap(prog: float, ref: float) -> float:
    if not (math.isfinite(prog) and math.isfinite(ref)) or ref == 0:
        return math.inf
    return abs(prog - ref) / abs(ref)


def numbers(prog: dict, ref: dict, rel_diff) -> dict:
    """The first steps' numbers: ``prog`` and ``ref`` as
    ``sut.Probe.numbers`` and ``reference.follow`` give them;
    ``rel_diff`` is the reference's."""
    out = {f"loss{t + 1}": gap(p, r) for t, (p, r)
           in enumerate(zip(prog["loss"], ref["loss"]))}
    for leaf in ("w", "V"):
        out[f"grad_{leaf}"] = gap(prog["grad"][leaf], ref["grad"][leaf])
        out[f"change_{leaf}"] = gap(prog["change"][leaf],
                                    ref["change"][leaf])
    out.update(rel_diff(prog["V"], prog["Vg"], ref["V"], ref["Vg"]))
    return out


def epoch_rows(rows_by_epoch, rows_per_epoch: int) -> float:
    """The largest share by which an epoch of the window missed the
    traffic's rows: a batch skipped or run twice shows here."""
    if not rows_by_epoch:
        return math.inf
    return max(abs(r - rows_per_epoch) for r in rows_by_epoch) \
        / rows_per_epoch


def judge(nums: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}): every number of ``NUMBERS``,
    every other number the run produced and every number the limits file
    names, each held to its limit; one without a limit, one that the run
    did not produce and one that is not finite fail."""
    names = list(NUMBERS)
    for n in list(nums) + [k for k in limits if not k.startswith("_")]:
        if n not in names:
            names.append(n)
    checked = {}
    ok = True
    for name in names:
        v = nums.get(name, math.inf)
        lim = limits.get(name)
        checked[name] = {"value": v if math.isfinite(v) else "inf",
                         "limit": lim}
        if lim is None or not math.isfinite(v) or v > lim:
            ok = False
    return ok, checked
