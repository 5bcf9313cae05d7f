"""Milliseconds of the provisioner's main class pass per 1,000 paths.

The span ``repro.greedy.classes`` of ``replicate_workload``: per budget
class the plan, the routed filter, the UPDATE batches and the exact
fallback (revalidation re-runs are not in it).
"""
from bench.harness.counters import per_kpath


def read(ctx):
    return per_kpath(ctx, "repro.greedy.classes.ns", 1e-6)
