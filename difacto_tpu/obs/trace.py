"""Nestable trace spans: Chrome trace-event JSON (Perfetto) and the
profiler's own timeline.

The timing half of the obs subsystem (metrics.py is the counting half;
:func:`difacto_tpu.obs.stage` joins the two at one boundary):
``with span("consumer.step", part=3):`` times its body once and hands
that one interval to two sinks.

**The span file** (``DIFACTO_TRACE=<path>`` or ``start()``): one complete
("X") event with microsecond timestamps. Events carry ``pid``/``tid``,
so a file holding events from the parent AND its producer worker
processes renders as one timeline in Perfetto / chrome://tracing —
worker parse -> pack -> ring wait -> consumer unpack -> device step,
side by side. The event buffer is bounded (default 200k events) —
overflow drops new events and counts them, never grows without limit.

Cross-process story: timestamps come from ``time.perf_counter`` (Linux
CLOCK_MONOTONIC — one clock for every process on the machine), so worker
events align with parent events with no offset bookkeeping. Worker
processes inherit ``DIFACTO_TRACE`` through the environment and collect
events in memory; the producer pool ships them to the parent through the
existing result queues (obs/proc.py) instead of writing files — only the
process that owns the trace writes it (child processes are marked with
``DIFACTO_OBS_CHILD=1`` and never install the atexit save). The pack
span's id additionally rides the shm-ring slot header
(data/shm_ring.py), so the consumer's unpack/step spans can point at the
exact producer span that built their batch (``producer_span`` arg).

**The profiler's timeline** (the shared clock): wherever ``jax`` is
already loaded, every span also opens a ``jax.profiler.TraceAnnotation``
under its own name (``StepTraceAnnotation`` when it carries a
``step_num`` arg). An annotation lands in WHATEVER profiler session is
live in the process — one started by ``DIFACTO_TRACE_DEVICE=<logdir>``
(:func:`start_device`), by ``jax.profiler.start_trace`` in a benchmark
harness, or by a profiling server — and costs well under a microsecond
when none is. The program's spans then sit in the same ``.xplane.pb`` as
the device's ``XLA Ops``: that is what lets a reader attribute a
device-idle gap to the innermost program span that covers it
(``perfbench/spans.py``, which also reads the 1.5 ms by which a v5e's
device planes run early against the host planes from the runtime's own
events in the same file). An annotation that is open when a
session starts or stops is not recorded (the profiler keeps complete
events only). ``jax`` is never imported from here, so a producer worker
that has not loaded it stays without it.

With neither sink on, a span is two clock reads and the annotation's
no-op: about a microsecond (tests/test_obs.py bounds it).
"""

from __future__ import annotations

import atexit
import itertools
import json
import logging
import os
import sys
import threading
import time
from typing import List, Optional
from ..utils.locktrace import mutex

_MAX_EVENTS = 200_000

_mu = mutex()
_events: List[dict] = []
_dropped = 0
_active = False
_path: Optional[str] = None
_device_on = False        # a profiler session started by start_device
_trace_id = 0
_span_ids = itertools.count(1)
_tls = threading.local()  # per-thread span stack


def active() -> bool:
    return _active


def trace_id() -> int:
    return _trace_id


def set_trace_id(tid: int) -> None:
    """Adopt a parent process's trace id (propagated through
    pack_stream.StreamSpec into producer workers)."""
    global _trace_id
    # lint: ok(data-race) write-once setup before producer workers span
    _trace_id = int(tid)


def start(path: Optional[str] = None,
          trace_id_: Optional[int] = None) -> None:
    """Begin collecting span events. ``path`` (optional) is where
    :func:`save` / the atexit hook writes the Chrome trace JSON."""
    global _active, _path, _trace_id
    # lint: ok(data-race) GIL-atomic on/off flip; spans tolerate either
    _active = True
    if path:
        _path = path
    _trace_id = (trace_id_ if trace_id_ is not None
                 else _trace_id or (os.getpid() << 16)
                 # lint: ok(wall-clock) id entropy, not a duration
                 | int(time.time()) % (1 << 16))


def stop() -> None:
    global _active
    _active = False


def start_device(logdir: str) -> None:
    """Start the JAX profiler into ``logdir`` (``DIFACTO_TRACE_DEVICE``):
    the operator's knob for a session of the program's own. Spans need
    no switching on — they annotate any live session. A trace that was
    asked for and cannot start RAISES: a run that silently carries on
    without its trace is a measurement nobody took."""
    global _device_on
    import jax
    try:
        os.makedirs(logdir, exist_ok=True)
        jax.profiler.start_trace(logdir)
    except Exception as e:
        raise RuntimeError(
            f"DIFACTO_TRACE_DEVICE={logdir!r}: the device trace could "
            f"not start ({e})") from e
    # lint: ok(data-race) write-once setup before any span thread
    _device_on = True


def stop_device() -> None:
    global _device_on
    if not _device_on:
        return
    _device_on = False
    import jax
    try:
        jax.profiler.stop_trace()
    except Exception as e:  # pragma: no cover - teardown shield
        logging.getLogger(__name__).warning(
            "device trace stop failed: %s", e)


def current_span_id() -> int:
    """The innermost open span's id on this thread (0 outside any)."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else 0


def last_span_id() -> int:
    """The most recently CLOSED span's id on this thread — how a caller
    that consumed a span-wrapped producer (e.g. the ring writer stamping
    the slot header with the pack span) names the span that just ran."""
    return getattr(_tls, "last", 0)


def add_event(ev: dict) -> None:
    global _dropped
    with _mu:
        if len(_events) >= _MAX_EVENTS:
            _dropped += 1
            return
        _events.append(ev)


def add_events(evs: List[dict]) -> None:
    """Merge events shipped from a child process (obs/proc.py)."""
    global _dropped
    if not evs:
        return
    with _mu:
        room = _MAX_EVENTS - len(_events)
        _events.extend(evs[:room])
        _dropped += max(0, len(evs) - room)


def drain_events() -> List[dict]:
    """Take (and clear) the collected events — how worker processes hand
    their spans to the parent through the result queue."""
    global _events
    with _mu:
        out, _events = _events, []
    return out


def _annotation(name: str, args: dict):
    """The profiler annotation of a span, or None where ``jax`` is not
    loaded in this process (never imported for this)."""
    prof = sys.modules.get("jax.profiler")
    cls = getattr(prof, "TraceAnnotation", None)
    if cls is None:
        return None
    if "step_num" in args:
        # JAX's step marker: the profiler's per-step device timeline
        # aligns with the span's cadence
        return prof.StepTraceAnnotation(name, **args)
    return cls(name, **args)


class span:
    """``with span(name, **args) as sid:`` — time the body once; record a
    complete event in the span file when that is on, and annotate the
    live profiler session when there is one. Nesting is per-thread; the
    event carries its span id, parent span id and the run's trace id,
    plus the keyword args (ints/strings only — they go straight into the
    JSON and the annotation). ``seconds`` holds the body's duration
    after the close.

    ``begin()``/``end()`` are the explicit form for a boundary that
    crosses functions (the learner's ``epoch_turn``); such a span may
    outlive the span that was open around its ``begin``."""

    __slots__ = ("name", "args", "sid", "seconds", "_parent", "_ann",
                 "_t0")

    def __init__(self, name: str, **args) -> None:
        self.name = name
        self.args = args
        self.sid = 0
        self.seconds = 0.0
        self._t0 = None

    def __enter__(self) -> int:
        if _active:
            stack = getattr(_tls, "stack", None)
            if stack is None:
                stack = _tls.stack = []
            self.sid = next(_span_ids)
            self._parent = stack[-1] if stack else 0
            stack.append(self.sid)
        self._ann = _annotation(self.name, self.args)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self.sid

    def __exit__(self, *exc) -> None:
        t0, self._t0 = self._t0, None
        self.seconds = time.perf_counter() - t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        if not self.sid:
            return
        stack = getattr(_tls, "stack", None)
        if stack:
            # a begin()/end() span may close after its neighbours
            if stack[-1] == self.sid:
                stack.pop()
            elif self.sid in stack:
                stack.remove(self.sid)
        _tls.last = self.sid
        add_event({"name": self.name, "ph": "X", "ts": t0 * 1e6,
                   "dur": self.seconds * 1e6, "pid": os.getpid(),
                   "tid": threading.get_ident() & 0xFFFFFFFF,
                   "args": {"span_id": self.sid, "parent": self._parent,
                            "trace_id": _trace_id, **self.args}})

    def begin(self) -> "span":
        self.__enter__()
        return self

    def end(self) -> float:
        """Close a begun span (idempotent) -> its seconds."""
        if self._t0 is not None:
            self.__exit__(None, None, None)
        return self.seconds


def save(path: Optional[str] = None) -> Optional[str]:
    """Write the collected events as Chrome trace JSON (loadable in
    Perfetto: ui.perfetto.dev, or chrome://tracing). Returns the path
    written, or None when there is nowhere to write."""
    path = path or _path
    if not path:
        return None
    with _mu:
        events = list(_events)
        dropped = _dropped
    doc = {"traceEvents": events, "displayTimeUnit": "ms",
           "otherData": {"trace_id": _trace_id, "dropped_events": dropped}}
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


def _maybe_start_from_env() -> None:
    path = os.environ.get("DIFACTO_TRACE", "")
    dev = os.environ.get("DIFACTO_TRACE_DEVICE", "")
    if not path and not dev:
        return
    if os.environ.get("DIFACTO_OBS_CHILD"):
        # producer worker: collect in memory, ship via the result queue
        # (obs/proc.py) — never write the parent's trace file; the JAX
        # profiler is the parent's too (workers own no device)
        start()
        return
    start(path or None)
    if path:
        atexit.register(save)
    if dev:
        # one profiler session per process, closed at exit so the
        # device trace flushes into <logdir> next to the span file
        start_device(dev)
        atexit.register(stop_device)


_maybe_start_from_env()
