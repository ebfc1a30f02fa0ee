"""The benchmark's harness: what every cell shares.

Nothing in this package names a configuration, a traffic mix or a
metric.  Those are files of their own under ``bench/``, found by the
names ``BENCHMARK.json`` gives (:mod:`bench.harness.spec`).
"""
