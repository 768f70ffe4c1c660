"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; sharding correctness is validated
on host-platform virtual devices (the driver separately dry-runs the
multi-chip path via __graft_entry__.dryrun_multichip).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pathlib

import pytest

# Known-environment guards (ISSUE 12 satellite): device-count / platform
# dependent suites degrade to explicit SKIPS on boxes that cannot run
# them, instead of joining the failure set and masking real regressions.
#
# Two-process jax.distributed runs (launch.py -n 2 workers) need a second
# CPU core: on a 1-core container the pair starves and
# multihost_utils.process_allgather fails inside the worker rather than
# testing anything. Sharding tests that only need the 8-device VIRTUAL
# mesh (this file's XLA flag) are unaffected and must not use this mark.
two_process_launch = pytest.mark.skipif(
    (os.cpu_count() or 1) < 2,
    reason="two-process jax.distributed run needs >= 2 CPU cores "
           "(1-core boxes fail in process_allgather, a known "
           "environment limit, not a code regression)")

def pytest_configure(config):
    # registered here (no pytest.ini): `slow` gates tier-2-only tests
    # out of the tier-1 `-m 'not slow'` run; `chaos` tags the
    # fault-injection resilience suite (tests/test_chaos.py) — IN
    # tier-1, selectable alone with `-m chaos`
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection resilience suite (runs in tier-1)")


@pytest.fixture(scope="session")
def rcv1_path() -> str:
    """First 100 rows of the public rcv1.binary dataset (libsvm format) —
    the same fixture the reference's golden tests use (tests/README.md)."""
    return str(pathlib.Path(__file__).parent / "data" / "rcv1_100.libsvm")


def write_uniform_libsvm(path, rows: int = 200, width: int = 8,
                         id_space: int = 300, seed: int = 7) -> str:
    """Uniform-width libsvm data: every row has exactly ``width`` valued
    features, so the panel layout (ops/batch.py panel_width) engages and
    mesh/SPMD tests exercise the panel + chunked-run step instead of COO."""
    import numpy as np
    rng = np.random.RandomState(seed)
    with open(path, "w") as f:
        for _ in range(rows):
            ids = np.sort(rng.choice(id_space, width, replace=False))
            vals = rng.rand(width)
            f.write(str(rng.randint(0, 2)) + " " + " ".join(
                f"{j}:{v:.4f}" for j, v in zip(ids, vals)) + "\n")
    return str(path)
