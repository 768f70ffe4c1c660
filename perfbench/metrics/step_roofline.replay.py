"""Reader of ``step_roofline.replay``: see ``perfbench/layers.py``."""
from perfbench.layers import step_roofline as read  # noqa: F401
