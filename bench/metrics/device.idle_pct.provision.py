"""Share of the provisioning calls' time in which no op ran on the device.

1 - (device-busy union inside the calls' spans) / (union of the spans),
from the profiler trace of the window, in %.
"""
from bench.harness.trace import busy_ns, length


def read(ctx):
    spans = ctx["spans"]
    total = length(spans)
    if not total or not len(ctx["trace"].start):
        return None
    return 100.0 * (1.0 - busy_ns(ctx["trace"], spans) / total)
