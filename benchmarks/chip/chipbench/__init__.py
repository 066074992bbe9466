"""Harness of the on-chip serving benchmark.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own under ``benchmarks/chip/configs``,
``traffic`` and ``metrics``; this package is the code that reads them.
"""
