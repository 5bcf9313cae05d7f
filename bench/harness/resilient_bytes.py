"""Bytes one run of the k-resilience gate's masked re-walk must move, from
its shapes alone.

One run walks every path of a call under each of ``D`` loss cases.  Per
case it reads the packed holder words (``n + 1`` rows of ``W`` uint32
words), masked by the case, and the case's failover home of each of the
``n`` objects; per path it reads the path's objects and length, and per
position the object's holder words and failover home; it writes each
path's distributed-traversal count.
"""
from __future__ import annotations

I32 = 4


def resilient_walk_bytes(D: int, P: float, L: int, W: int, n: int) -> float:
    """Bytes one masked re-walk of ``P`` paths under ``D`` cases moves."""
    per_case = (
        (n + 1) * W * I32          # holder words, masked by the case
        + n * I32                  # failover homes
        + P * L * I32 + P * I32    # path objects, lengths
        + P * L * (W + 1) * I32    # words and home of each position
        + P * I32                  # traversal count written
    )
    return D * per_case
