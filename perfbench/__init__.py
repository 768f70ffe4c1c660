"""The benchmark of difacto-tpu: harness, traffic, reference and readers.

Everything that decides a number lives here, where a PR that claims a
gain cannot change it. ``run.py`` is the entry; ``BENCHMARK.json`` at the
root of the repo names the cells.
"""
