"""``obs.stage``: one boundary for a span and its counter.

Before this module a stage was timed twice: ``t0 = time.perf_counter()``
… ``counter.inc(time.perf_counter() - t0)`` for ``stage_seconds_total``
and, somewhere near but not at the same lines, a ``trace.span`` for the
timeline — so the counter and the span of one stage could disagree, and
some stages had one without the other. :class:`stage` is a span
(obs/trace.py) that on close adds ITS OWN duration to
``stage_seconds_total{stage=name}`` in the given registry: one start,
one end, two sinks. It is the one way to time a boundary.

Also here: the process-wide ``jax.monitoring`` listener that feeds
``stage_seconds_total{stage=compile}`` and ``compiles_total{fn}``.
"""

from __future__ import annotations

import logging
import weakref

from ..utils.locktrace import mutex
from . import names
from .trace import span

log = logging.getLogger(__name__)


def stage_counter(registry, name: str):
    """``stage_seconds_total{stage=name}`` of ``registry`` (cached on
    the registry: the lookup sits on per-batch paths)."""
    cache = registry._stage_series
    series = cache.get(name)
    if series is None:
        series = cache[name] = registry.counter(
            names.STAGE_METRIC, names.STAGE_HELP).labels(stage=name)
    return series


class stage(span):
    """``with stage(registry, "transfer", part=3) as st:`` — a span named
    after the stage (``names.STAGE_SPAN`` keeps the older span names of
    the producer stages) whose duration is added to the stage's counter
    on close; ``st.seconds`` is that duration. ``also`` names further
    stages that receive the SAME duration (``step`` is produced as
    ``dispatch`` + ``fetch_wait`` this way, so the three cannot drift).
    ``begin()``/``end()`` as for a span."""

    __slots__ = ("_series",)

    def __init__(self, registry, name: str, also=(), **args) -> None:
        super().__init__(names.STAGE_SPAN.get(name, name), **args)
        self._series = [stage_counter(registry, n)
                        for n in (name, *also)]

    def __enter__(self) -> "stage":
        super().__enter__()
        return self

    def __exit__(self, *exc) -> None:
        super().__exit__(*exc)
        for series in self._series:
            series.inc(self.seconds)


# ------------------------------------------------------------- compiles
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_sinks: "weakref.WeakSet" = weakref.WeakSet()
_sinks_mu = mutex()
_listening = False


def _on_duration(event: str, secs: float, fun_name: str = "",
                 **_kw) -> None:
    if event != _COMPILE_EVENT:
        return
    # which step recompiled (a cap rung crossed mid-run) has its answer
    # in the log, in #metrics and in the epoch's record
    log.info("compiled %s in %.3f s", fun_name, secs)
    with _sinks_mu:      # a compile thread against a learner's init
        sinks = list(_compile_sinks)
    for registry in sinks:
        stage_counter(registry, names.COMPILE).inc(secs)
        registry.counter(names.COMPILES, names.COMPILES_HELP).labels(
            fn=fun_name).inc()


def watch_compiles(registry) -> None:
    """Feed backend-compile seconds (any thread's, the background
    ``pair-exec-compile`` thread's included) into ``registry``'s
    ``stage_seconds_total{stage=compile}``, and a count by the compiled
    function's name into its ``compiles_total{fn}``, for as long as the
    registry lives; each compile is also one INFO line. ONE listener a
    process however many learners register: JAX offers no way to take a
    listener off again."""
    global _listening
    stage_counter(registry, names.COMPILE)   # the series exists at 0
    with _sinks_mu:
        _compile_sinks.add(registry)
        first, _listening = not _listening, True
    if first:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
