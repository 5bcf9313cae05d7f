"""Gate dispatches of the same-policy prune's sweep per 1,000 paths.

The counter ``repro.greedy.prune.dispatches``: one per dispatch of the
sweep's gate, that is one per independent candidate group on a device
backend (one per candidate under the serial reference sweep).  None on a
program without the counter.
"""
from bench.harness.counters import per_kpath


def read(ctx):
    return per_kpath(ctx, "repro.greedy.prune.dispatches")
