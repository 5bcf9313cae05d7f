"""The access walk (paper Eqn 1-2), vectorised over paths.

A path starts at the home server of its root.  Each later access is local
when the current server holds a copy of the object; otherwise it is a
distributed traversal, and the walk moves to a holder of the object:

``home_first``    the object's home server;
``nearest_copy``  among the holders, those that also hold the path's next
                  object when there are any (one-step lookahead), else
                  all holders; the home server when it is among them,
                  else the lowest server id.

``mask`` is bool [n_objects, n_servers] (originals included), ``home``
int [n_objects].
"""
from __future__ import annotations

import numpy as np

POLICIES = ("home_first", "nearest_copy")


def walk_latencies(objects, lengths, mask, home, policy: str) -> np.ndarray:
    """Distributed traversals per path, int64 [P]."""
    if policy not in POLICIES:
        raise ValueError(f"unknown policy {policy!r}")
    objects = np.asarray(objects, np.int64)
    lengths = np.asarray(lengths, np.int64)
    home = np.asarray(home, np.int64)
    P, L = objects.shape
    out = np.zeros(P, np.int64)
    if P == 0:
        return out
    S = mask.shape[1]
    ids = np.arange(S)
    cur = home[np.maximum(objects[:, 0], 0)]
    for x in range(1, L):
        live = x < lengths
        v = np.maximum(objects[:, x], 0)
        holders = mask[v]                                   # [P, S]
        local = holders[np.arange(P), cur]
        remote = live & ~local
        out += remote
        if policy == "home_first":
            nxt = home[v]
        else:
            cand = holders
            if x + 1 < L:
                has_next = (x + 1) < lengths
                both = holders & mask[np.maximum(objects[:, x + 1], 0)]
                use_both = has_next & both.any(axis=1)
                cand = np.where(use_both[:, None], both, holders)
            home_in = cand[np.arange(P), home[v]]
            lowest = np.where(cand, ids[None, :], S).min(axis=1)
            nxt = np.where(home_in, home[v], lowest)
        cur = np.where(remote, nxt, cur)
    return out


def query_latencies(path_lats, query_ids, n_queries: int) -> np.ndarray:
    """Latency of each query: the max over its paths (Def 4.3)."""
    out = np.zeros(n_queries, np.int64)
    np.maximum.at(out, np.asarray(query_ids, np.int64), path_lats)
    return out
