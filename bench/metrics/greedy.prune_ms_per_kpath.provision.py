"""Milliseconds of the same-policy prune and re-pack per 1,000 paths.

The span ``repro.greedy.prune`` of ``replicate_workload``: the prune's
engine, walk and candidates (``repro.greedy.prune.pack``), its sweep
(``.sweep``) and the re-pack of the pruned mask (``.repack``).
"""
from bench.harness.counters import per_kpath


def read(ctx):
    return per_kpath(ctx, "repro.greedy.prune.ns", 1e-6)
