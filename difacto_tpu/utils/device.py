"""Device binding for entry points: where compiled programs are cached
and which device the process actually got.

Entry points (``python -m difacto_tpu``, chip_smoke.py, perfbench/) call
:func:`place_compile_cache` before their first backend touch and report
:func:`bound_device` once bound, so a run that came up on the CPU
because it could not get the chip says so in its first lines instead of
in its throughput.
"""

from __future__ import annotations

import os
from typing import Optional

# <checkout>/.jax_cache: derived from this file's location, never from
# the cwd, a tempdir, a pid or the clock — the directory is part of the
# cache key, so a path that moves between runs never hits
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it. ``JAX_COMPILATION_CACHE_DIR`` in the environment wins and
    nothing is set in code (jax reads the variable itself); otherwise
    the cache lives in ``<checkout>/.jax_cache``. A process pinned to
    the CPU backend (``JAX_PLATFORMS=cpu``: tests, virtual meshes) keeps
    none unless the environment names one: its compiles are seconds,
    and jaxlib 0.9.0 reloads an XLA:CPU entry with a page of
    target-feature-mismatch errors ("could lead to ... SIGILL")."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    if (jax.config.jax_platforms or "").lower() == "cpu":
        return None
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def bound_device() -> dict:
    """``{platform, device_kind, count}`` as JAX reports the devices this
    process bound (binds the backend on first call)."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "count": len(devs)}
