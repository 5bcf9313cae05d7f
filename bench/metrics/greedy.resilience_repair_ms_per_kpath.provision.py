"""Milliseconds of the k-resilience gate's case repairs per 1,000 paths.

The spans ``repro.greedy.resilience.repair``, one per violating loss case
and round: the orphan re-homing, the masked words, the class plan, the
routed filter and the UPDATE batches over the case's violating paths.
"""
from bench.harness.counters import per_kpath


def read(ctx):
    return per_kpath(ctx, "repro.greedy.resilience.repair.ns", 1e-6)
