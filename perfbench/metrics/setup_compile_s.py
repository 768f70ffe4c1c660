"""Reader of ``setup_compile_s``: see ``perfbench/layers.py``."""
from perfbench.layers import setup_compile_s as read  # noqa: F401
