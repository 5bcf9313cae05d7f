"""The UPDATE step's share of the chip's HBM roofline, in %.

Work: every UPDATE program run in the traced window is one batch; its
bytes come from the batch's shapes (``bench.harness.update_bytes``), plus
one word read-modify-write per replica added.  Time: the device time of
the ops of the UPDATE programs, and, where the routed gate runs as a
program of its own, of the gate run that precedes each UPDATE run.  The
programs are matched by the names the trace prints: the separate-
dispatch path's ``jit__update_batch_core`` with its gate
(``jit__root_home`` then ``jit__routed_counts_impl`` or
``jit_pallas_routed...``), and the fused path's
``jit__fused_update_batch``, the Pallas kernel inside it.
"""
from bench.harness.trace import program_runs
from bench.harness.update_bytes import additions_bytes, update_batch_bytes

UPDATE = ("jit__update_batch_core", "jit__fused_update_batch")
GATE = ("jit__routed_counts_impl", "jit_pallas_routed")


def _is(prog, names):
    return any(prog.startswith(n) for n in names)


def read(ctx):
    u = ctx["summary"]["update"]
    runs = program_runs(ctx["trace"])
    n_batches = 0
    t_ns = 0
    for i, (prog, _, _, busy) in enumerate(runs):
        if not _is(prog, UPDATE):
            continue
        n_batches += 1
        t_ns += busy
        j = i - 1
        if u["gate"] and j >= 0 and _is(runs[j][0], GATE):
            t_ns += runs[j][3]
            if j >= 1 and runs[j - 1][0].startswith("jit__root_home"):
                t_ns += runs[j - 1][3]
    if not n_batches or not t_ns:
        return None
    work = n_batches * update_batch_bytes(
        u["B"], u["L"], u["W"], u["C"], u["Hp1"], u["S"], u["gate"])
    work += additions_bytes(u["additions"])
    return 100.0 * work / (t_ns / 1e9) / ctx["peaks"]["hbm_bytes_per_s"]
