"""Blocking device-to-host readbacks of the provisioner per 1,000 paths.

The counter ``repro.engine.d2h_calls``: one per call of the program's
``repro.engine.to_host``, the one readback path of ``replicate_workload``,
its prune and the helpers they call; each is a host round trip.
"""
from bench.harness.counters import per_kpath


def read(ctx):
    return per_kpath(ctx, "repro.engine.d2h_calls")
