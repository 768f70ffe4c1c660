"""Reader of ``leg_other_pct.replay``: share of the busy time under ``unpack``,
``evaluate`` or no leg
(``perfbench/spans.py``)."""
from perfbench.spans import leg_other_pct as read  # noqa: F401
