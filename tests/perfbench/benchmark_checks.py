"""The checks that the tests make of a benchmark's configurations and
cells, as functions of the benchmark (``BENCHMARK.json`` as a dict) and
the root its files lie under: the repository's benchmark is held to
them, and so is a tiny one with one more configuration added by files
alone. What a check asks of a configuration's learner it asks the
configuration's driver (``run.load_driver``): the numbers its comparison
produces (``names``) and the faults it plants (``FAULTS``). Not a test
file."""

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
# the seed the recorded learner keys were built with
KWARGS_SEED = 2**31 + 9


def served_by_files(b: dict, root: str) -> None:
    """Every configuration, cell and metric of ``b`` is served by a file
    under ``root``: a configuration its plain reference, its driver and a
    control that the driver can run; a cell its traffic, and a limits
    file that holds every number its driver's comparison produces; a
    per-layer metric its reader and the cells it is reported for."""
    from perfbench import check, sut
    from perfbench import run as R
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    bdir = os.path.join(root, b["paths"][0])
    drivers, configs = {}, {}
    for c in b["configs"]:
        assert NAME.match(c["name"])
        with open(os.path.join(root, c["file"])) as f:
            cfg = json.load(f)
        # compared with its plain reference: the file names it, and it
        # lies in the benchmark's own directory
        ref = os.path.join(root, cfg["reference"])
        assert os.path.exists(ref) and cfg["reference"].startswith(
            b["paths"][0] + "/")
        assert set(c["reduced"]) <= set(cfg)
        assert set(c["reduced"]) == set(cfg["about"]["reduced"]) | {
            k for k in cfg["about"]["assumed"] if k != "why"}
        drv = R.load_driver(root, cfg)
        # the control: one of the driver's planted faults by name, or
        # the program's keys for its nearest precision below (for the
        # SGD learner, its storage types)
        control = dict(cfg["control"])
        assert control.pop("why") and cfg["precision"]
        if "fault" in control:
            assert control == {"fault": control["fault"]}
            assert control["fault"] in drv.FAULTS
        elif drv is sut:
            assert control and set(control) <= {"V_dtype", "slot_dtype"}
        else:
            assert control
        drivers[c["name"]] = drv
        configs[c["name"]] = {k: v for k, v in cfg.items()
                              if k not in R.META}
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    cells = [w["name"] for w in b["workloads"]]
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            bdir, "traffic", w["traffic"] + ".json"))
        # the limits file names every number the driver's comparison
        # produces; a table of the SGD learner's, those of its own
        # layout with epoch_rows, and none of the other layout's
        with open(os.path.join(bdir, "limits", w["name"] + ".json")) as f:
            limited = {k for k in json.load(f) if not k.startswith("_")}
        drv = drivers[w["config"]]
        mine = set(drv.names(configs[w["config"]]))
        assert mine and limited >= mine
        if drv is sut:
            other = set(check.NUMBERS + check.FLAT_NUMBERS) - mine
            assert "epoch_rows" in limited and not limited & other
        mine = [m["name"] for m in R.metrics_of(b, "end_to_end",
                                                w["name"])]
        assert "setup_s" in mine and len(mine) >= 2
        assert R.metrics_of(b, "per_layer", w["name"])
    for m in b["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert R.load_reader(bdir, m["name"]) is not None
        assert m["workloads"] and set(m["workloads"]) <= set(cells)
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


def driven(b: dict, root: str, config: str, recorded: dict) -> None:
    """``config`` resolves to the driver that trains its learner. One that
    names no driver is driven by ``perfbench.sut`` and builds the SGD
    learner with the keys, byte for byte, of ``recorded[config]``. One
    that names a driver names a file under the benchmark's directory
    whose learner the program registers, and that learner, built on the
    CPU with no data from the keys the driver gives it for each of the
    configuration's cells (and with its control, where that is keys),
    leaves none of them unknown."""
    from perfbench import sut
    from perfbench import run as R
    cells = [w["name"] for w in b["workloads"] if w["config"] == config]
    assert cells
    loaded = R.load_cell(b, root, cells[0])
    cfg = loaded["config"]
    drv = R.load_driver(root, cfg)
    cfg_kw = {k: v for k, v in cfg.items() if k not in R.META}
    if "driver" not in cfg:
        assert drv is sut and drv.__name__ == "perfbench.sut"
        assert drv.LEARNER == "sgd"
        got = json.dumps(drv.learner_kwargs(cfg_kw, loaded["traffic"],
                                            "DATA", KWARGS_SEED))
        assert got == recorded[config]
        return
    full = os.path.normpath(os.path.join(root, cfg["driver"]))
    assert full.startswith(os.path.join(os.path.normpath(root),
                                        b["paths"][0], ""))
    assert os.path.isfile(full)
    assert sut.learner_known(drv.LEARNER)
    from difacto_tpu.learners import Learner
    control = {k: v for k, v in cfg["control"].items()
               if k not in ("why", "fault")}
    for cell in cells:
        traffic = R.load_cell(b, root, cell)["traffic"]
        for override in [None] + ([control] if control else []):
            kw = drv.learner_kwargs(cfg_kw, traffic, "DATA", KWARGS_SEED,
                                    override)
            remain = Learner.create(drv.LEARNER).init(
                [(k, str(v)) for k, v in kw.items()])
            assert not remain, (
                f"{cell}: keys the {drv.LEARNER} learner does not know: "
                f"{remain}")
