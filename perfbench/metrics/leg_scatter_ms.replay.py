"""Reader of ``leg_scatter_ms.replay``: device time a step under scope
``scatter`` (``perfbench/spans.py``)."""
from perfbench import spans


def read(ctx):
    return spans.leg_ms(ctx, "scatter")
