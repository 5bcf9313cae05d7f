"""Milliseconds of the routed revalidation rounds per 1,000 paths.

The span ``repro.greedy.revalidate`` of ``replicate_workload``: the
routed walk of every path against the pass's scheme, the re-runs of the
violating paths and the dirty-row re-walks.
"""
from bench.harness.counters import per_kpath


def read(ctx):
    return per_kpath(ctx, "repro.greedy.revalidate.ns", 1e-6)
