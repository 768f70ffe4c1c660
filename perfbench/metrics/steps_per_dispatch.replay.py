"""Reader of ``steps_per_dispatch.replay``: steps over enqueues in the
window (2: every step ran paired), from the ``epoch.counts`` records
(``perfbench/counts.py``)."""
from perfbench.counts import steps_per_dispatch as read  # noqa: F401
