"""Smoke tests for the driver entry ``__graft_entry__``. Round 1 shipped
it broken because nothing executed it in CI; these tests run it the way
the driver does, on tiny shapes. (The benchmark, perfbench/, has its own
tests under tests/perfbench/.)
"""

import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent


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
