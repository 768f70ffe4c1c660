"""Reader of ``epoch_turn_span_ms.replay``: mean length of the
``epoch_turn`` spans that lie whole between the marks
(``perfbench/counts.py``)."""
from perfbench.counts import epoch_turn_span_ms as read  # noqa: F401
