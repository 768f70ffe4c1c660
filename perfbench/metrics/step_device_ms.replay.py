"""Reader of ``step_device_ms.replay``: see ``perfbench/layers.py``."""
from perfbench.layers import step_device_ms as read  # noqa: F401
