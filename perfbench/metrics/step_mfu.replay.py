"""Reader of ``step_mfu.replay``: see ``perfbench/layers.py``."""
from perfbench.layers import step_mfu as read  # noqa: F401
