"""The seam between the harness and the learner a configuration names.

A configuration's ``"driver"`` names the module that trains its learner
(``run.load_driver``); without it the SGD learner's ``perfbench/sut.py``
drives, as every benchmark configuration does. On the CPU at a tiny size:
the L-BFGS learner runs a whole cell through ``run.run_cell`` under a
test-only driver (``seam_lbfgs_driver.py``) and is held to a plain
objective (``seam_lbfgs_reference.py``), which a planted fault fails; a
driver that is no file, or whose learner the program does not register
(read from the learners' source, which no run imports before its rows
are made), ends the run before any data is made; the benchmark's
configurations resolve to ``perfbench.sut`` with the learner keys and
the ``calibrate.py --part first`` readings recorded before a
configuration could name its driver (at 619158e, ``data/``); the
L-BFGS configuration added to the benchmark's files by files and entries
alone passes the checks its configurations are held to
(``benchmark_checks.py``), which ask its driver, ``calibrate.py`` reads
it through its driver's ``reading``, and its traced run reports the
per-layer metrics whose lists it joins; and a per-layer reader is
handed the cell, its configuration and the data's summary."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import benchmark_checks as checks
import perfbench_tiny as tiny

HERE = os.path.dirname(os.path.abspath(__file__))
SEAM_CFG, SEAM_CELL = "lbfgs_seam", "lbfgs_seam.batch"
SEAM_LIMITS = {"objv": 1e-5, "epoch_rows": 0}


def _with_cell(bench: dict, config: dict, traffic: str, cell: str,
               root: str, per_layer=("setup_compile_s",
                                     "setup_stage_s")) -> dict:
    """``bench`` with one more configuration and one cell of it, which
    reports ``replay_ex_per_s`` and the per-layer metrics ``per_layer``;
    the configuration's file under ``root``. Only entries are added."""
    name = cell.split(".")[0]
    path = os.path.join("perfbench", "configs", name + ".json")
    with open(os.path.join(root, path), "w") as f:
        json.dump(config, f)
    bench = json.loads(json.dumps(bench))
    bench["configs"].append({
        "name": name, "source": SEAM_SOURCE, "file": path,
        "reduced": sorted(config["about"]["reduced"]),
        "why": "the upstream's batch learner on the tiny traffic"})
    bench["workloads"].append({
        "name": cell, "config": name, "traffic": traffic, "chips": 1,
        "why": "whole passes over the tiny traffic's rows"})
    next(m for m in bench["end_to_end"]
         if m["name"] == "replay_ex_per_s")["workloads"].append(cell)
    for m in bench["per_layer"]:
        if m["name"] in per_layer:
            m["workloads"].append(cell)
    return bench


SEAM_SOURCE = ("Chen, Wang, Smola: Large-scale L-BFGS using MapReduce, "
               "NIPS 2014; github.com/dmlc/difacto src/lbfgs/lbfgs_param.h")
# the configuration as a benchmark would hold it: the tiny root cuts it
# by its driver's ``TINY``
SEAM_CONFIG = {
    "loss": "fm", "V_dim": 0, "m": 10, "l2": 0.1, "batch_size": 65536,
    "data_format": "rec",
    "precision": "float32 weights, gradients and history",
    "reference": "perfbench/seam_lbfgs_reference.py",
    "driver": "perfbench/seam_lbfgs_driver.py",
    "control": {"fault": "half_the_loss",
                "why": "the learner has no lower precision to run"},
    "about": {"reduced": {"data_format": "rec members made from the seed"},
              "assumed": {"why": "none"}}}


def _add_seam(bench: dict, root: str) -> dict:
    """``bench`` with the L-BFGS configuration and its cell, added under
    ``root`` by files and entries alone: the test-only driver and
    reference beside the harness's files, a traffic file (the ``replay``
    mix's rows with no learner keys), a limits file and the
    configuration."""
    bdir = os.path.join(root, "perfbench")
    for name in ("seam_lbfgs_driver.py", "seam_lbfgs_reference.py"):
        shutil.copy(os.path.join(HERE, name), bdir)
    with open(os.path.join(bdir, "traffic", "replay.json")) as f:
        traffic = json.load(f)
    traffic["learner"] = {}
    with open(os.path.join(bdir, "traffic", "batch_seam.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bdir, "limits", SEAM_CELL + ".json"), "w") as f:
        json.dump(SEAM_LIMITS, f)
    return _with_cell(bench, SEAM_CONFIG, "batch_seam", SEAM_CELL, root)


@pytest.fixture()
def seam(tmp_path):
    """(bench, root): the tiny benchmark with an L-BFGS cell over the tiny
    traffic's 512 rows (``_add_seam``), its configuration cut by its
    driver."""
    root = tiny.make_root(str(tmp_path))
    bench = _add_seam(tiny.bench(), root)
    tiny.cut_config(root, SEAM_CFG)
    return bench, root


def _run(bench, root, cell, seed=7, seconds=0.2, trace=False):
    from perfbench import run as R
    lines = {}
    res = R.run_cell(bench, root, cell, seed, seconds, trace,
                     require_tpu=False,
                     out=lambda k, v: lines.__setitem__(k, v))
    return res, lines


def test_a_batch_learner_runs_through_the_seam(seam):
    res, lines = _run(*seam, SEAM_CELL, seed=2**31 + 9)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"replay_ex_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert set(res["checked"]) == set(SEAM_LIMITS)
    assert res["checked"]["objv"]["value"] < 1e-6
    win = json.loads(lines["window"])
    # an epoch is a whole number of passes over the 512 rows, one for
    # each trial of the line search
    assert win["rows"] % 512 == 0 and win["rows"] >= 512 * win["epochs"]
    assert win["warm_epochs"] >= 5           # the history is full
    assert win["producer_mode"] is None and win["stages_s"] == {}
    ref = json.loads(lines["reference"])
    assert ref["program"]["objv"] == pytest.approx(
        ref["reference"]["objv"], rel=1e-6)


def _half_the_loss(monkeypatch):
    """The objective each tile reports leaves out half its rows' loss."""
    from difacto_tpu.learners import lbfgs
    real = lbfgs.logit_objv
    monkeypatch.setattr(lbfgs, "logit_objv",
                        lambda pred, batch: 0.5 * real(pred, batch))


def _no_regulariser(monkeypatch):
    """The objective leaves out the l2 term: the gradient keeps it."""
    import jax.numpy as jnp
    from difacto_tpu.learners import lbfgs
    real = lbfgs.LBFGSLearner._build_steps

    def build(self):
        real(self)
        self._reg_objv = lambda weights, reg_c: jnp.float32(0.0)

    monkeypatch.setattr(lbfgs.LBFGSLearner, "_build_steps", build)


@pytest.mark.parametrize("plant", [_half_the_loss, _no_regulariser],
                         ids=["half_the_loss", "no_regulariser"])
def test_a_planted_fault_is_not_correct(seam, monkeypatch, plant):
    plant(monkeypatch)
    res, _ = _run(*seam, SEAM_CELL)
    assert res["correct"] is False
    assert res["checked"]["objv"]["value"] > 10 * SEAM_LIMITS["objv"]
    assert res["checked"]["epoch_rows"]["value"] == 0.0


@pytest.mark.parametrize("driver, learner", [
    ("perfbench/no_such_driver.py", None),
    ("perfbench/seam_unregistered.py", "no_such_learner"),
], ids=["no_such_file", "learner_not_registered"])
def test_a_driver_that_cannot_drive_ends_the_run_before_any_data(
        seam, driver, learner):
    bench, root = seam
    if learner is not None:
        with open(os.path.join(root, driver), "w") as f:
            f.write(f"LEARNER = {learner!r}\nN_STEPS = 3\n")
    with open(os.path.join(root, "perfbench", "configs",
                           SEAM_CFG + ".json")) as f:
        config = dict(json.load(f), driver=driver)
    bench = _with_cell(bench, config, "batch_seam", "bad_driver.batch",
                       root)
    with pytest.raises(SystemExit, match='"driver"') as e:
        _run(bench, root, "bad_driver.batch")
    assert driver in str(e.value)
    if learner is not None:
        assert learner in str(e.value)
    assert not os.path.exists(os.path.join(root, ".perfbench_run"))


@pytest.mark.parametrize("name", ["sgd", "lbfgs", "bcd", "LBFGS",
                                  "no_such_learner"])
def test_the_registry_read_from_source_is_the_programs(name):
    """``sut.learner_known`` reads the learners' source, so that no run
    imports them before its rows are made; it says what importing them
    would."""
    from perfbench import sut
    from difacto_tpu.learners import Learner
    try:
        Learner.create(name)
        known = True
    except ValueError:
        known = False
    assert sut.learner_known(name) is known


def test_reading_the_registry_imports_none_of_the_learners():
    code = ("import sys; sys.path.insert(0, %r); from perfbench import sut;"
            " sut.learner_known('sgd');"
            " print('difacto_tpu.learners' in sys.modules)" % tiny.ROOT)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.stdout.strip() == "False", out.stderr


def _benchmark_configs():
    return [c["name"] for c in tiny.bench()["configs"]]


def _recorded_kwargs() -> dict:
    with open(os.path.join(HERE, "data", "learner_kwargs.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("config", _benchmark_configs())
def test_each_benchmark_configuration_is_driven_by_sut(config):
    """A configuration that names no driver is driven by
    ``perfbench.sut`` and builds the learner with the keys, byte for
    byte, that the harness built it with before a configuration could
    name one; one that names its driver is held to what
    ``benchmark_checks.driven`` asks of it."""
    checks.driven(tiny.bench(), tiny.ROOT, config, _recorded_kwargs())


def test_a_configuration_added_by_files_alone_passes_the_checks(tmp_path):
    """The benchmark's own files with the L-BFGS configuration, its cell,
    traffic, limits, driver and reference added as files and entries
    alone (``_add_seam``): the checks the benchmark's configurations are
    held to ask each configuration's driver, and all of them pass."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(tiny.ROOT, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = _add_seam(tiny.bench(), root)
    checks.served_by_files(bench, root)
    for c in bench["configs"]:
        checks.driven(bench, root, c["name"], _recorded_kwargs())


def test_the_tiny_root_cuts_a_configuration_by_its_driver(seam):
    _, root = seam
    with open(os.path.join(root, "perfbench", "configs",
                           SEAM_CFG + ".json")) as f:
        cut = json.load(f)
    assert {k: cut[k] for k in ("m", "batch_size", "l2")} == {
        "m": 5, "batch_size": 64, "l2": 0.1}
    assert "hash_capacity" not in cut and "V_dtype" not in cut
    with open(os.path.join(root, "perfbench", "configs",
                           "fm_v64_criteo.json")) as f:
        flagship = json.load(f)
    from perfbench import sut
    assert {k: flagship[k] for k in sut.TINY} == sut.TINY


def test_the_checks_catch_sgd_keys_in_a_batch_learners_traffic(seam):
    """The SGD cells' traffic under the L-BFGS configuration: the keys
    of the HBM batch cache and of the producer reach a learner that does
    not know them."""
    bench, root = seam
    bench = json.loads(json.dumps(bench))
    next(w for w in bench["workloads"]
         if w["name"] == SEAM_CELL)["traffic"] = "replay"
    with pytest.raises(AssertionError, match="device_cache_mb"):
        checks.driven(bench, root, SEAM_CFG, {})


def test_sgd_traffic_keys_are_the_sgd_learners_alone():
    """The keys by which the tiny root knows an SGD cell's traffic are
    keys the SGD learner takes and the L-BFGS learner does not."""
    import dataclasses
    from difacto_tpu.learners import lbfgs, sgd
    sgd_keys = {f.name for f in dataclasses.fields(sgd.SGDLearnerParam)}
    lbfgs_keys = {f.name for p in (lbfgs.LBFGSLearnerParam,
                                   lbfgs.LBFGSUpdaterParam)
                  for f in dataclasses.fields(p)}
    keys = set(tiny.SGD_TRAFFIC_KEYS) | {"producer_mode"}
    assert keys <= sgd_keys and not keys & lbfgs_keys


def test_the_tiny_root_cuts_only_the_sgd_cells_traffic(seam):
    _, root = seam
    tdir = os.path.join(root, "perfbench", "traffic")
    for name in os.listdir(tdir):
        with open(os.path.join(tdir, name)) as f:
            learner = json.load(f)["learner"]
        sgd = bool(set(learner) & set(tiny.SGD_TRAFFIC_KEYS))
        assert sgd == (name != "batch_seam.json")
        assert ("producer_mode" in learner) == sgd


def test_calibrate_reads_the_seam_cell_through_its_driver(seam,
                                                          monkeypatch):
    """``calibrate.py --cpu --faults`` on the L-BFGS cell: the driver's
    ``reading`` runs to the probe's epoch; the program's objective is
    the plain one, and each planted fault reads over the limit."""
    from perfbench import calibrate
    bench, root = seam
    monkeypatch.setattr(calibrate, "ROOT", root)
    row = calibrate.reading(bench, SEAM_CELL, 2**31 + 11, None, None, True,
                            False)
    assert row["part"] == "probe" and row["correct"] is True
    assert set(row["numbers"]) == {"objv"}
    assert row["numbers"]["objv"] < SEAM_LIMITS["objv"]
    assert set(row["faults"]) == {"half_the_loss", "no_regulariser"}
    for fault, nums in row["faults"].items():
        assert nums["objv"] > 10 * SEAM_LIMITS["objv"], fault
    assert row["program"]["objv"] == pytest.approx(
        row["reference"]["objv"], rel=1e-6)
    assert not os.listdir(os.path.join(root, ".perfbench_run"))


def test_calibrate_ends_at_once_on_a_driver_without_reading(seam, capsys,
                                                           monkeypatch):
    from perfbench import calibrate
    bench, root = seam
    with open(os.path.join(HERE, "seam_lbfgs_driver.py")) as f:
        src = f.read()
    with open(os.path.join(root, "perfbench", "seam_no_reading.py"),
              "w") as f:
        f.write(src[:src.index("\ndef reading(")])
    with open(os.path.join(root, "perfbench", "configs",
                           SEAM_CFG + ".json")) as f:
        config = dict(json.load(f), driver="perfbench/seam_no_reading.py")
    bench = _with_cell(bench, config, "batch_seam", "no_reading.batch",
                       root)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    monkeypatch.setattr(calibrate, "ROOT", root)
    with pytest.raises(SystemExit, match=r"reading\(") as e:
        calibrate.main(["--workload", "no_reading.batch", "--seeds", "3",
                        "--cpu"])
    assert "perfbench/seam_no_reading.py" in str(e.value)
    assert not os.path.exists(os.path.join(root, ".perfbench_run"))
    assert capsys.readouterr().out == ""


def test_a_traced_batch_cell_reports_the_metrics_it_joins(seam):
    """With the trace on, the L-BFGS cell reports the per-layer metrics
    whose lists it joined and no reader of the SGD learner's legs,
    stages or records."""
    res, _ = _run(*seam, SEAM_CELL, seconds=0.5, trace=True)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"setup_compile_s", "setup_stage_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())


@pytest.mark.parametrize("layout", ["fused", "flat"])
def test_calibrate_first_reads_what_it_read_before_the_seam(
        tmp_path, monkeypatch, layout):
    """``calibrate.py --part first`` on the flagship's tiny cell, seed 7:
    the numbers, and both sides they come from, as recorded."""
    from perfbench import calibrate
    root = tiny.make_root(str(tmp_path),
                          **(tiny.FLAT if layout == "flat" else {}))
    monkeypatch.setattr(calibrate, "ROOT", root)
    row = calibrate.reading(tiny.bench(), "fm_v64_criteo.replay", 7, None,
                            "first", False, False)
    with open(os.path.join(HERE, "data", "calibrate_first.json")) as f:
        want = json.load(f)[layout]
    got = json.loads(json.dumps({k: row[k] for k in want}))
    assert got == want


_CTX_READER = '''import json, os


def read(ctx):
    with open(os.path.join(os.path.dirname(__file__), "..", "ctx.json"),
              "w") as f:
        json.dump({"cell": ctx["cell"], "config": ctx["config"],
                   "data": ctx["data"], "keys": sorted(ctx)}, f)
    return ctx["data"]["uniq_per_step"]
'''


def test_readers_are_handed_the_cell_its_configuration_and_data(tmp_path):
    from perfbench import run as R
    root = tiny.make_root(str(tmp_path))
    with open(os.path.join(root, "perfbench", "metrics",
                           "ctx_seen.replay.py"), "w") as f:
        f.write(_CTX_READER)
    bench = json.loads(json.dumps(tiny.bench()))
    bench["per_layer"].append({
        "name": "ctx_seen.replay", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "test",
        "moves": "replay_ex_per_s", "workloads": ["fm_v64_criteo.replay"]})
    res, lines = _run(bench, root, "fm_v64_criteo.replay", seconds=5,
                      trace=True)
    assert res["correct"] is True
    # the readers that find nothing on the CPU still find nothing
    assert set(res["metrics"]) == {"setup_compile_s", "setup_stage_s",
                                   "ctx_seen.replay"}
    with open(os.path.join(root, "perfbench", "ctx.json")) as f:
        seen = json.load(f)
    assert seen["keys"] == sorted(["res", "trace", "least", "steps",
                                   "compile_s", "chips", "cell", "config",
                                   "data"])
    assert seen["cell"] == "fm_v64_criteo.replay"
    with open(os.path.join(root, "perfbench", "configs",
                           "fm_v64_criteo.json")) as f:
        config = json.load(f)
    assert seen["config"] == {k: v for k, v in config.items()
                              if k not in R.META}
    data = json.loads(lines["data"])
    assert seen["data"]["rows"] == data["rows_per_epoch"] == 512
    assert seen["data"]["batch"] == 64 and seen["data"]["width"] == 39
    assert seen["data"]["n_members"] == data["steps_per_epoch"] == 8
    assert res["metrics"]["ctx_seen.replay"]["value"] == pytest.approx(
        data["uniq_features_per_step"], abs=0.05)
