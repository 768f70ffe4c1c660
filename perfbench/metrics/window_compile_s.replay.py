"""Reader of ``window_compile_s.replay``: the window's change of
``stage_seconds_total{stage=compile}``
(``perfbench/spans.py``)."""
from perfbench.spans import window_compile_s as read  # noqa: F401
