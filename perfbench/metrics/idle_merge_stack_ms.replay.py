"""Reader of ``idle_merge_stack_ms.replay``: device-idle time an epoch
under span ``merge.stack``
(``perfbench/counts.py``)."""
from perfbench.counts import idle_merge_stack_ms as read  # noqa: F401
