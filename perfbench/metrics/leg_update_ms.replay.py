"""Reader of ``leg_update_ms.replay``: device time a step under scope
``update`` (``perfbench/spans.py``)."""
from perfbench import spans


def read(ctx):
    return spans.leg_ms(ctx, "update")
