"""Reader of ``idle_epoch_turn_ms.replay``: device-idle time an epoch
inside span ``epoch_turn``
(``perfbench/spans.py``)."""
from perfbench.spans import idle_epoch_turn_ms as read  # noqa: F401
