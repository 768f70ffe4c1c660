"""The arithmetic of the per-layer metrics.

Each metric of ``BENCHMARK.json``'s ``per_layer`` has a reader of its own
in ``metrics/<name>.py``; most are one line that names a function here.
A reader takes the run's context and returns a number, or ``None`` where
it finds nothing to read: the harness then leaves the metric out.

The context ``ctx`` holds: ``res`` (what ``sut.drive`` kept), ``trace``
(``tracered.reduce``'s result, or None), ``least`` (``work.least_seconds``
of one step), ``steps`` (steps in the window), ``compile_s`` (backend
compile seconds up to the window), ``chips``.
"""

from __future__ import annotations


def setup_compile_s(ctx):
    return ctx.get("compile_s")


def setup_stage_s(ctx):
    return ctx["res"].get("epoch0_s")


def _busy(ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("busy_s") or not ctx.get("steps"):
        return None
    return tr["busy_s"]


def step_device_ms(ctx):
    b = _busy(ctx)
    return None if b is None else 1e3 * b / ctx["steps"]


def step_roofline(ctx):
    """The least time the chips could take for a step's necessary work,
    over the device time a step took."""
    b = _busy(ctx)
    if b is None or not ctx.get("least"):
        return None
    return 100.0 * ctx["least"]["seconds"] / (b / ctx["steps"])


def step_mfu(ctx):
    """The same least time over the whole step, idle included: the
    window's time a step."""
    if not ctx.get("least") or not ctx.get("steps") or not ctx.get("trace"):
        return None
    per_step = ctx["trace"]["window_s"] / ctx["steps"]
    return 100.0 * ctx["least"]["seconds"] / per_step


def device_idle_pct(ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("busy_s") or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
