"""Reader of ``epoch_turn_ms.replay``: the window's change of
``stage_seconds_total{stage=epoch_turn}`` an epoch
(``perfbench/spans.py``)."""
from perfbench.spans import epoch_turn_ms as read  # noqa: F401
