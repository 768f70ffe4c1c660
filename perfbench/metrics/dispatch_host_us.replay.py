"""Reader of ``dispatch_host_us.replay``: the window's change of
``stage_seconds_total{stage=dispatch}`` a step
(``perfbench/spans.py``)."""
from perfbench.spans import dispatch_host_us as read  # noqa: F401
