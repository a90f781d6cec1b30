"""The benchmark: harness, traffic, yardsticks and references.

Nothing here is imported by the program; the program is imported only by
``builders/`` and ``adapters/``. See PERF.md for how to add a cell, a
configuration or a per-layer metric by adding files alone.
"""
