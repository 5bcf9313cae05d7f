"""The k-resilience gate's masked re-walk's share of the chip's HBM
roofline, in %.

Work: every run of the re-walk program in the traced window walks a
call's paths under every loss case; its bytes come from the shapes in the
driver's summary (``bench.harness.resilient_bytes``).  Time: the device
time of the ops of those runs.  The program is matched by the name the
trace prints: ``jit__resilient_routed_vmap``, ``jit__resilient_home_vmap``
or ``jit__resilient_dp_vmap``, by the routing policy.  None where no such
run is in the trace.
"""
from bench.harness.resilient_bytes import resilient_walk_bytes
from bench.harness.trace import program_runs

WALK = ("jit__resilient_routed_vmap", "jit__resilient_home_vmap",
        "jit__resilient_dp_vmap")


def read(ctx):
    shapes = ctx["summary"].get("resilient_walk")
    if not shapes:
        return None
    runs = [r for r in program_runs(ctx["trace"]) if r[0].startswith(WALK)]
    t_ns = sum(r[3] for r in runs)
    if not runs or not t_ns:
        return None
    work = len(runs) * resilient_walk_bytes(**shapes)
    return 100.0 * work / (t_ns / 1e9) / ctx["peaks"]["hbm_bytes_per_s"]
