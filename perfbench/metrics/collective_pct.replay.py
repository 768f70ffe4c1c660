"""Reader of ``collective_pct.replay``: share of the fullest chip's busy
time under a collective operation (``perfbench/exchange.py``)."""
from perfbench.exchange import collective_pct as read  # noqa: F401
