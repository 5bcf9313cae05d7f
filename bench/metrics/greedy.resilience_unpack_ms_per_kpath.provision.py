"""Milliseconds of the k-resilience gate's host unpacks per 1,000 paths.

The spans ``repro.greedy.resilience.unpack``: the whole-mask readback and
unpack of each repair round (the orphan filter) and the final one that
becomes the returned scheme.
"""
from bench.harness.counters import per_kpath


def read(ctx):
    return per_kpath(ctx, "repro.greedy.resilience.unpack.ns", 1e-6)
