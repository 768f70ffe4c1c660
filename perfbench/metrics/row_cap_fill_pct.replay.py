"""Reader of ``row_cap_fill_pct.replay``: distinct table rows of the window's
steps over their row caps, from the program's ``epoch.counts`` records
(``perfbench/counts.py``)."""
from perfbench.counts import row_cap_fill_pct as read  # noqa: F401
