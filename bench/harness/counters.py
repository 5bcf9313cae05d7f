"""Per-layer metrics read from the program's own counters.

The program records into ``repro.obs.REGISTRY`` when its telemetry plane
is on, as it is in a traced run: each ``repro.obs.span`` adds its wall
time to ``<span>.ns`` and its count to ``<span>.n``, and the provisioner's
readbacks count ``repro.engine.d2h_calls``.  ``ctx["counters"]`` holds
what each counter moved over the window.
"""


def per_kpath(ctx, counter: str, scale: float = 1.0):
    """``counter``'s move over the window times ``scale``, per 1,000 paths
    the calls processed; None when it did not move (a program without
    that span or counter)."""
    moved = ctx["counters"].get(counter)
    paths = ctx["summary"].get("paths_processed", 0)
    if not moved or not paths:
        return None
    return scale * moved * 1000.0 / paths
