"""Reader of ``chunk_cap_fill_pct.replay``: chunks the window's steps need
over their chunk caps, from the program's ``epoch.counts`` records
(``perfbench/counts.py``)."""
from perfbench.counts import chunk_cap_fill_pct as read  # noqa: F401
