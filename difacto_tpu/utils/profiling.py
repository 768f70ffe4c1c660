"""Profiling: wall-clock section timers + JAX device profiler hooks.

The reference only had ``dmlc::GetTime`` wall-clock spans (epoch timer
sgd_learner.cc:55,145; per-part times in WorkloadPool) and the spmv_perf
harness. Here:

- :class:`Timer` — named cumulative wall-clock sections (host side);
- :func:`device_trace` — context manager around ``jax.profiler.trace``
  producing a TensorBoard/XProf trace of the XLA execution (the TPU-native
  answer to "where did the step time go").
"""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Dict, Iterator


class Timer:
    """Cumulative named sections: ``with timer("pull"): ...``; report()."""

    def __init__(self) -> None:
        self.total: Dict[str, float] = defaultdict(float)
        self.count: Dict[str, int] = defaultdict(int)

    @contextlib.contextmanager
    def __call__(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.total[name] += time.perf_counter() - t0
            self.count[name] += 1

    def report(self) -> str:
        rows = sorted(self.total.items(), key=lambda kv: -kv[1])
        return "\n".join(
            f"{name:24s} {tot:8.3f}s  x{self.count[name]}"
            for name, tot in rows)


def device_trace(log_dir: str):
    """Context manager capturing a device profile into ``log_dir`` (view
    with xprof/TensorBoard) — jax's own, so a trace that was asked for
    and cannot start or stop raises: a run that silently carries on
    without its trace is a measurement nobody took."""
    import jax
    return jax.profiler.trace(log_dir)
