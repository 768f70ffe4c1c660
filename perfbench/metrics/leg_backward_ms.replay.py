"""Reader of ``leg_backward_ms.replay``: device time a step under scope
``backward`` (``perfbench/spans.py``)."""
from perfbench import spans


def read(ctx):
    return spans.leg_ms(ctx, "backward")
