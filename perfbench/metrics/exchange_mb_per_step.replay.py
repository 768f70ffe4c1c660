"""Reader of ``exchange_mb_per_step.replay``: the all-reduce operand a step
as the program moves it, from the ``epoch.counts`` records
(``perfbench/counts.py``)."""
from perfbench.counts import exchange_mb_per_step as read  # noqa: F401
