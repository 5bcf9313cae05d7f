"""Bytes one batch of the UPDATE step must move, from its shapes alone.

The UPDATE of a batch (paper Alg 2, batched) reads, for each of its ``B``
paths of ``L`` positions, the path's objects, length and budget, the home
server and storage cost of each object, and the packed holder words of
each object (``W`` uint32 words, one bit per server) for the bit tests;
it reads one block of the candidate table per path (``C`` candidates x
``H + 1`` subpaths, one byte each); and it writes each path's cost,
failure and skip flags, its chosen additions (``L x (H + 1)`` bytes), the
first object and server of each subpath, the per-server load, and, for
each replica it adds, a read-modify-write of one holder word.  When the
routing policy gates the batch, the gate walk reads each object's holder
words and home server once more, and hands each path's routed latency
to the UPDATE.

The count is the same whichever implementation runs the step (separate
gate and UPDATE programs, one fused program, or a Pallas kernel): it is
the work, not the traffic of any one implementation, so a share of peak
bandwidth computed from it can only be an under-estimate of what that
implementation moved.
"""
from __future__ import annotations

I32 = 4


def update_batch_bytes(B: int, L: int, W: int, C: int, Hp1: int, S: int,
                       gate: bool) -> int:
    """Bytes one batch's UPDATE reads and writes, additions excluded."""
    reads = (
        B * L * I32          # path objects
        + B * I32            # lengths
        + B * I32            # budgets t_q
        + B * L * I32        # home server of each object
        + B * L * I32        # storage cost f of each object
        + B * L * W * I32    # holder words of each object
        + B * C * Hp1        # candidate-table block of each path
        + S * I32            # per-server load
    )
    writes = (
        B * I32              # cost
        + 2 * B              # failed, skipped flags
        + B * L * Hp1        # chosen additions
        + 2 * B * Hp1 * I32  # first object, server of each subpath
        + S * I32            # new load
    )
    if gate:
        reads += B * L * W * I32 + B * L * I32 + B * I32
        writes += B * I32
    return reads + writes


def additions_bytes(n_additions: int) -> int:
    """Read-modify-write of one holder word per replica added."""
    return 2 * n_additions * I32
