"""Reader of ``idle_unattributed_pct.replay``: share of the device's
idle time under no program span
(``perfbench/spans.py``)."""
from perfbench.spans import idle_unattributed_pct as read  # noqa: F401
