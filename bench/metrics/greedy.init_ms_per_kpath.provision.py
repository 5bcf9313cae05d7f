"""Host milliseconds of the provisioner's set-up per 1,000 paths.

The span ``repro.greedy.init`` of ``replicate_workload``: the path
dedup, the host and packed schemes built from the sharding, the storage
load, the gate and fused set-up.
"""
from bench.harness.counters import per_kpath


def read(ctx):
    return per_kpath(ctx, "repro.greedy.init.ns", 1e-6)
