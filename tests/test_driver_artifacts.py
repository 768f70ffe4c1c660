"""Smoke tests for the two driver-graded artifacts: bench.py and
__graft_entry__. Round 1 shipped both broken because nothing executed
them in CI; these tests run them the way the driver does, on tiny shapes.
"""

import json
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent


def _run(cmd, extra_env=None):
    env = dict(os.environ)
    if extra_env:
        env.update(extra_env)
    return subprocess.run(cmd, cwd=str(REPO), env=env,
                          capture_output=True, text=True, timeout=600)


def test_bench_device_mode_smoke():
    # --device-only: the default e2e window is 1.8M rows, far too slow
    # for a CPU smoke (the e2e path gets its own tiny-window test below)
    proc = _run([sys.executable, "bench.py", "--device-only",
                 "--steps", "2", "--batch-size", "128", "--uniq", "256",
                 "--capacity", "1024", "--vdim", "4"])
    assert proc.returncode == 0, proc.stderr
    line = proc.stdout.strip().splitlines()[-1]
    rec = json.loads(line)
    assert rec["value"] > 0
    assert set(rec) >= {"metric", "value", "unit", "vs_baseline"}
    # every line names the device it was taken on; a CPU run prints no
    # share of any chip's peak
    assert rec["device"] == {"platform": "cpu", "device_kind": "cpu",
                             "count": 8}
    assert "bw_fraction" not in rec["roofline"]
    assert rec["kernel"]["measured"] == ["off", "jnp"]


def test_bench_mesh_mode_smoke():
    # --mesh DPxFS runs the same step as a sharded program over a mesh —
    # on the 8 virtual CPU devices the conftest env provides.
    proc = _run([sys.executable, "bench.py", "--device-only",
                 "--mesh", "2x4", "--steps", "2", "--batch-size", "128",
                 "--uniq", "256", "--capacity", "1024", "--vdim", "4"])
    assert proc.returncode == 0, proc.stderr
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["value"] > 0
    assert "mesh2x4" in rec["metric"]


def test_bench_e2e_smoke():
    proc = _run([sys.executable, "bench.py", "--e2e",
                 "--e2e-rows", "2000", "--e2e-batch", "256",
                 "--capacity", "4096", "--vdim", "4"])
    assert proc.returncode == 0, proc.stderr
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["value"] > 0
    assert rec["config"]["rows"] == 2000


def test_graft_entry_single_chip():
    import jax

    import __graft_entry__

    fn, args = __graft_entry__.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)


def test_dryrun_multichip_in_process():
    # conftest gives this process 8 virtual CPU devices: in-process path
    import __graft_entry__

    __graft_entry__.dryrun_multichip(8)


def test_dryrun_multichip_subprocess_fallback():
    # a fresh interpreter without the XLA flag has 1 CPU device, so
    # dryrun_multichip(4) must take the subprocess fallback and succeed
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    code = ("import jax; jax.config.update('jax_platforms', 'cpu'); "
            "import __graft_entry__; __graft_entry__.dryrun_multichip(4)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=str(REPO),
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr
