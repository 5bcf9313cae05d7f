"""Milliseconds of the host unpack of the packed words per 1,000 paths.

The span ``repro.greedy.unpack`` of ``replicate_workload``: the slice
and readback of the device words and their unpacking into the host
bool mask after revalidation.
"""
from bench.harness.counters import per_kpath


def read(ctx):
    return per_kpath(ctx, "repro.greedy.unpack.ns", 1e-6)
