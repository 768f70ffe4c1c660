"""Reader of ``exchange_roofline.replay``: least time for the rows a
chip must take in over the collective time a step
(``perfbench/exchange.py``)."""
from perfbench.exchange import exchange_roofline as read  # noqa: F401
