"""Dynamic micro-batcher: amortize device dispatch over many small requests.

The standard adaptive-batching design (Clipper / TF-Serving style): a
bounded admission queue feeds one batching thread that collects requests
until ``batch_size`` rows or ``max_delay_ms`` elapse — whichever first —
then concatenates them into ONE RowBlock and runs the bucketed predict
executor once. Overload is explicit, never silent: a full queue SHEDS the
request at admission (``submit`` returns None, the front-end answers
``!shed``), so queue depth — and therefore worst-case queueing latency —
stays bounded at ``queue_cap`` rows of work instead of growing without
limit.

``ServeStats`` is the observability half: per-request latency percentiles
(p50/p95/p99 over a sliding window), batch occupancy, queue depth and
shed counters, published through the utils/reporter.py contract (the
reference's out-of-band progress channel) on a time throttle, and
snapshot-able on demand (the server's ``#stats`` control line,
tools/loadgen.py).
"""

from __future__ import annotations

import collections
import logging
import threading
import time
from concurrent.futures import Future
from typing import Callable, Optional

import numpy as np
import queue

from ..data.rowblock import RowBlock
from ..utils import faultinject, shared
from ..utils.reporter import Reporter
from ..utils.locktrace import mutex

log = logging.getLogger("difacto_tpu")


class ServeStats:
    """Serving counters + latency, REGISTRY-BACKED (difacto_tpu/obs).

    The counters behind ``#stats`` now live in an obs registry — one per
    server instance so concurrent servers in a process never blur — which
    is also what the ``#metrics`` Prometheus endpoint renders (serve/
    server.py). The ``snapshot()`` wire format is byte-compatible with
    the hand-rolled counters it replaced: same keys, same meanings; the
    exact sliding-window percentiles (p50/p95/p99 over the last
    ``window`` responses) are kept for ``#stats``, while the registry's
    ``serve_latency_seconds`` histogram carries the whole-run quantiles
    Prometheus-side. This registry is always enabled — ``#stats`` is a
    wire contract, not optional telemetry — so ``DIFACTO_OBS=off`` only
    disables the default-registry instrumentation, never serving stats.
    """

    # RACETRACE opt-in (utils/shared.py): the statically GuardedBy
    # fields of this class, traced when DIFACTO_RACETRACE=1
    _lat = shared.attr()
    _last_report = shared.attr()

    def __init__(self, reporter: Optional[Reporter] = None,
                 report_every_s: float = 30.0, window: int = 8192,
                 registry=None):
        from ..obs import Registry
        self.obs = registry if registry is not None \
            else Registry(enabled=True)
        self._mu = mutex()              # latency window + report throttle
        self._lat = collections.deque(maxlen=window)  # seconds
        self._t0 = time.monotonic()
        self._last_report = self._t0
        self._report_every = report_every_s
        self.reporter = reporter
        self._req_c = self.obs.counter(
            "serve_requests_total", "rows admitted into the micro-batcher").labels()
        self._resp_c = self.obs.counter(
            "serve_responses_total", "rows scored and answered")
        self._shed_c = self.obs.counter(
            "serve_shed_total", "rows shed at admission (queue full or "
            "draining)")
        self._err_c = self.obs.counter(
            "serve_errors_total", "rows rejected or failed")
        self._batch_c = self.obs.counter(
            "serve_batches_total", "micro-batches dispatched")
        self._rows_c = self.obs.counter(
            "serve_rows_batched_total", "rows across dispatched "
            "micro-batches")
        self._lat_h = self.obs.histogram(
            "serve_latency_seconds",
            "admit-to-answer latency per scored row")
        self._occ_h = self.obs.histogram(
            "serve_batch_rows", "micro-batch occupancy (rows per batch)",
            bounds=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048,
                    4096))
        self._qd_g = self.obs.gauge(
            "serve_queue_depth", "admission queue depth at the last "
            "batch flush")
        self._qdm_g = self.obs.gauge(
            "serve_queue_depth_max", "high-water admission queue depth")

    def record_admit(self, rows: int = 1) -> None:
        self._req_c.inc(rows)

    def record_shed(self, rows: int = 1) -> None:
        self._shed_c.inc(rows)

    def record_error(self, rows: int = 1) -> None:
        self._err_c.inc(rows)

    def record_batch(self, rows: int, queue_depth: int) -> None:
        self._batch_c.inc()
        self._rows_c.inc(rows)
        self._occ_h.observe(rows)
        self._qd_g.set(queue_depth)
        s = self._qdm_g.labels()
        s.set(max(s.value(), queue_depth))

    def shed_rate(self) -> float:
        """Lifetime shed fraction — cheap enough for every ``#health``
        poll (two counter reads), which is where the rolling-restart
        gate (serve/fleet.py) watches for a shed spike."""
        n_shed = self._shed_c.value()
        offered = self._req_c.value() + n_shed
        return round(n_shed / max(offered, 1), 4)

    def record_latency(self, seconds: float) -> None:
        self._resp_c.inc()
        self._lat_h.observe(seconds)
        with self._mu:
            self._lat.append(seconds)

    def snapshot(self) -> dict:
        with self._mu:
            lat = np.asarray(self._lat, dtype=np.float64)
        n_requests = int(self._req_c.value())
        n_responses = int(self._resp_c.value())
        n_shed = int(self._shed_c.value())
        n_batches = int(self._batch_c.value())
        elapsed = max(time.monotonic() - self._t0, 1e-9)
        offered = n_requests + n_shed
        out = {
            "requests": n_requests,
            "responses": n_responses,
            "shed": n_shed,
            "errors": int(self._err_c.value()),
            "shed_rate": round(n_shed / max(offered, 1), 4),
            "qps": round(n_responses / elapsed, 1),
            "batches": n_batches,
            "batch_occupancy": round(
                self._rows_c.value() / max(n_batches, 1), 2),
            "queue_depth": int(self._qd_g.value()),
            "queue_depth_max": int(self._qdm_g.value()),
        }
        if len(lat):
            p50, p95, p99 = np.percentile(lat, [50, 95, 99]) * 1e3
            out.update(p50_ms=round(float(p50), 3),
                       p95_ms=round(float(p95), 3),
                       p99_ms=round(float(p99), 3),
                       max_ms=round(float(lat.max() * 1e3), 3))
        return out

    def maybe_report(self) -> None:
        """Throttled publish through the Reporter channel — the serving
        analog of the training progress rows."""
        if self.reporter is None:
            return
        now = time.monotonic()
        with self._mu:
            if now - self._last_report < self._report_every:
                return
            self._last_report = now
        self.reporter.report(self.snapshot())


class MicroBatcher:
    """Collect -> concat -> score, with explicit shed on overload.

    ``predict_fn(blk) -> scores[blk.size]`` runs on the single batching
    thread (the executor's dispatch contract). ``queue_cap`` bounds
    admission in ROWS of queued work, the quantity that actually sets
    queueing delay (a row costs what a row costs, however the requests
    arrive grouped). ``predict_fn`` is re-read at every flush, which is
    what makes the blue/green executor swap one attribute assignment
    (server.swap_executor): the in-flight batch finishes on the function
    it started with, the next flush dispatches on the replacement.
    """

    # RACETRACE opt-in (utils/shared.py): `_rows_queued`/`_busy` are
    # statically GuardedBy _mu, `_alive` is a suppressed stop flag —
    # the tier-1 gate cross-checks real accesses against those facts
    _rows_queued = shared.attr()
    _busy = shared.attr()
    _alive = shared.attr()

    def __init__(self, predict_fn: Callable[[RowBlock], np.ndarray],
                 batch_size: int = 256, max_delay_ms: float = 2.0,
                 queue_cap: int = 1024,
                 stats: Optional[ServeStats] = None):
        self.predict_fn = predict_fn
        self.batch_size = batch_size
        self.max_delay_s = max_delay_ms / 1e3
        self.queue_cap = queue_cap
        self.stats = stats if stats is not None else ServeStats()
        self._q: "queue.Queue" = queue.Queue()
        self._rows_queued = 0          # admission-bounded under _mu
        self._mu = mutex()
        self._alive = False
        self._busy = False             # a batch is being scored right now
        self._thread: Optional[threading.Thread] = None

    # ---------------------------------------------------------- control
    def start(self) -> None:
        # lint: ok(data-race) monotonic stop flag (GIL-atomic bool): the
        # loop observes the False from close() on its next iteration
        self._alive = True
        self._thread = threading.Thread(target=self._loop,
                                        name="serve-batcher", daemon=True)
        self._thread.start()

    def close(self) -> None:
        self._alive = False
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        # fail any requests still queued so connection writers never hang
        while True:
            try:
                _, fut, _rows = self._q.get_nowait()
            except queue.Empty:
                break
            fut.set_exception(RuntimeError("serve batcher shut down"))

    # ----------------------------------------------------------- submit
    def submit(self, blk: RowBlock) -> Optional[Future]:
        """Admit a request (one or more rows). Returns a Future resolving
        to scores[blk.size], or None when the queue is full — the caller
        must surface the shed to the client (backpressure is explicit).
        ``batcher.enqueue`` is a chaos-harness injection point
        (utils/faultinject.py): ``err`` surfaces through the server as an
        ``!err`` reply, ``delay_ms`` models a stalled admission path."""
        faultinject.act_default(faultinject.fire("batcher.enqueue"))
        with self._mu:
            if self._rows_queued + blk.size > self.queue_cap:
                self.stats.record_shed(blk.size)
                return None
            self._rows_queued += blk.size
        fut: Future = Future()
        self.stats.record_admit(blk.size)
        self._q.put((blk, fut, blk.size))
        return fut

    @property
    def rows_queued(self) -> int:
        with self._mu:
            return self._rows_queued

    @property
    def idle(self) -> bool:
        """No queued rows and no batch mid-score — the drain loop's
        "all admitted work has resolved" condition (server.drain). One
        atomic snapshot under ``_mu``: reading the two fields unlocked
        could observe the decrement of a batch that is not busy YET and
        report idle with work in flight."""
        with self._mu:
            return self._rows_queued == 0 and not self._busy

    # ------------------------------------------------------------- loop
    def _collect(self):
        """One micro-batch: block for the first request, then fill until
        batch_size rows or the delay budget expires."""
        try:
            first = self._q.get(timeout=0.05)
        except queue.Empty:
            return []
        batch = [first]
        rows = first[2]
        deadline = time.monotonic() + self.max_delay_s
        while rows < self.batch_size:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            try:
                item = self._q.get(timeout=remaining)
            except queue.Empty:
                break
            batch.append(item)
            rows += item[2]
        return batch

    def _loop(self) -> None:
        while self._alive:
            batch = self._collect()
            if not batch:
                continue
            # busy BEFORE the queued-row decrement, in one _mu region:
            # the drain loop must never observe (rows_queued == 0,
            # busy == False) while this batch is still unscored
            rows = sum(r for _, _, r in batch)
            with self._mu:
                self._busy = True
                self._rows_queued -= rows
                depth = self._rows_queued
            try:
                self.stats.record_batch(rows, depth)
                try:
                    # one attribute read per flush: a concurrent
                    # swap_executor retargets the NEXT flush, never
                    # splits this one
                    scores = self.predict_fn(
                        RowBlock.concat([b for b, _, _ in batch]))
                except Exception as e:  # pragma: no cover - executor bug
                    log.exception("serve batch failed")
                    self.stats.record_error(rows)
                    for _, fut, _ in batch:
                        fut.set_exception(e)
                    continue
                o = 0
                for b, fut, r in batch:
                    fut.set_result(scores[o:o + r])
                    o += r
                self.stats.maybe_report()
            finally:
                with self._mu:
                    self._busy = False
