"""The repo's benchmark: the yardstick that later PRs may add to and not edit.

``run.py`` is the command ``BENCHMARK.json`` names. Everything that belongs to
one configuration, one traffic mix, one kind of cell or one per-layer metric
is a file of its own that the harness finds by name (``configs/``,
``traffic/``, ``kinds/``, ``layers/``).
"""
