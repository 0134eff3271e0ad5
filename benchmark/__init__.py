"""The benchmark: the yardstick every later PR is measured with.

``BENCHMARK.json`` at the root of the repo is the manifest; ``run.py`` is the
one command.  Everything that belongs to one configuration, one cell or one
per-layer metric is a file of its own, found by its name (README.md).
"""
