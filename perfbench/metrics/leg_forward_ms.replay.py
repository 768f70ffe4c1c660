"""Reader of ``leg_forward_ms.replay``: device time a step under scope
``forward`` (``perfbench/spans.py``)."""
from perfbench import spans


def read(ctx):
    return spans.leg_ms(ctx, "forward")
