"""Reader of ``setup_stage_s``: see ``perfbench/layers.py``."""
from perfbench.layers import setup_stage_s as read  # noqa: F401
