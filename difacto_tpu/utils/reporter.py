"""Progress reporter: the out-of-band node -> scheduler channel.

Equivalent of the reference's Reporter (include/difacto/reporter.h:14-56;
LocalReporter src/reporter/local_reporter.h). In the single-controller design
the "channel" is a callback, but the contract is kept — components call
``report(payload)``, whoever set the monitor receives it — so learners and
stores stay decoupled from the progress consumer, and a multi-host build can
swap in a DCN-backed implementation without touching them. The reference's
servers auto-report every 50 pushes (include/difacto/store.h:118-123);
``every`` reproduces that throttle.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Optional
from .locktrace import mutex


class Reporter:
    def __init__(self, every: int = 1):
        self._monitor: Optional[Callable[[int, Any], None]] = None
        self._mu = mutex()
        self._count = 0
        self._every = max(every, 1)

    def set_monitor(self, fn: Callable[[int, Any], None]) -> None:
        """fn(node_id, payload)."""
        # lint: ok(data-race) write-once wiring before the reporting
        # thread starts (the serve batcher's loop, the learner's run)
        self._monitor = fn

    def report(self, payload: Any, node_id: int = 0) -> int:
        """Deliver payload to the monitor (throttled); returns a sequence
        number like the reference's report timestamp."""
        with self._mu:
            self._count += 1
            seq = self._count
        if self._monitor is not None and seq % self._every == 0:
            self._monitor(node_id, payload)
        return seq
