"""The benchmark's own library: the yardstick that later PRs may not edit.

``run.py`` is the entry point. Everything that belongs to one
configuration, one traffic mix, one driver, one reducer or one metric is a
file of its own that :mod:`cfbench.catalog` finds by the name written in
``BENCHMARK.json``; nothing in this package names a cell, a configuration
or a metric.
"""
