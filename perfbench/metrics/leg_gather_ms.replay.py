"""Reader of ``leg_gather_ms.replay``: device time a step under scope
``gather`` (``perfbench/spans.py``)."""
from perfbench import spans


def read(ctx):
    return spans.leg_ms(ctx, "gather")
