"""Milliseconds of the k-resilience gate per 1,000 paths.

The span ``repro.greedy.resilience`` of ``replicate_workload``: the
failover homes, the masked re-walk of every loss case per round, the host
mask, the repair of each violating case, the replay into the live words
and the final unpack.  None on a call without resilience.
"""
from bench.harness.counters import per_kpath


def read(ctx):
    return per_kpath(ctx, "repro.greedy.resilience.ns", 1e-6)
