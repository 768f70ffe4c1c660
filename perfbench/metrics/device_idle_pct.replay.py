"""Reader of ``device_idle_pct.replay``: see ``perfbench/layers.py``."""
from perfbench.layers import device_idle_pct as read  # noqa: F401
