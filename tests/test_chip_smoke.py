"""Device binding of the entry points (chip_smoke.py, utils/device.py):
the chip proof must refuse a CPU backend before it does any work, and the
compile cache must land where the environment says — or at one fixed
place under the checkout, whatever the cwd."""

import os
import pathlib
import subprocess
import sys

import jax

from difacto_tpu.utils import device

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_chip_smoke_refuses_cpu_before_any_work(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          cwd=str(tmp_path), env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout
    # no data generated (its scratch dir would land in TMPDIR), nothing
    # compiled
    assert list(tmp_path.iterdir()) == []


def test_compile_cache_env_wins_and_config_stays_untouched(monkeypatch,
                                                           tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert device.place_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cpu_pinned_process_keeps_no_cache(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert jax.config.jax_platforms == "cpu"      # conftest
    assert device.place_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_under_the_checkout(tmp_path):
    # no JAX_PLATFORMS=cpu: a process pinned to the CPU keeps no cache
    # (the helper only reads config — nothing here binds a backend)
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS")}
    env["PYTHONPATH"] = str(REPO)
    code = ("import jax; from difacto_tpu.utils.device import "
            "place_compile_cache as p; "
            "print(p()); print(jax.config.jax_compilation_cache_dir)")
    seen = []
    for cwd in (tmp_path, REPO / "tests"):
        out = subprocess.run([sys.executable, "-c", code], cwd=str(cwd),
                             env=env, capture_output=True, text=True,
                             timeout=120, check=True).stdout.split()
        assert out[0] == out[1]
        seen.append(out[0])
    assert seen[0] == seen[1] == str(REPO / ".jax_cache")
