"""Milliseconds of the k-resilience gate's masked re-walks per 1,000 paths.

The spans ``repro.greedy.resilience.eval``, one per round: the stacked
failover homes uploaded, the re-walk of every path under every loss case
(``resilient_counts``) and its readback.
"""
from bench.harness.counters import per_kpath


def read(ctx):
    return per_kpath(ctx, "repro.greedy.resilience.eval.ns", 1e-6)
