"""Device programs the greedy driver runs per 1,000 paths provisioned.

Runs of jitted programs (the ``XLA Modules`` events of the profiler
trace) that start inside the provisioning calls' spans, per 1,000 paths
the calls processed.  Each run is a dispatch from the host, and most are
followed by a wait for its result, so the count follows the driver's
host round trips: per batch (gate, UPDATE, scatter), per revalidation
round and per pruned candidate, whichever implementation runs them.
"""
from bench.harness.trace import program_count


def read(ctx):
    paths = ctx["summary"].get("paths_processed", 0)
    runs = program_count(ctx["trace"], ctx["spans"])
    return 1000.0 * runs / paths if paths and runs else None
