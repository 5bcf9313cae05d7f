"""Sequential greedy replication (paper Alg 1 + Alg 2), one path at a time.

``update_path`` is the UPDATE of one path against the current scheme: the
path's server-local subpaths under the sharding function, every choice of
``t`` retained subpaths besides the root's (in ``itertools.combinations``
order), each non-retained subpath merged into the preceding retained one
by upward replication, the cheapest choice applied (the first on a tie).
Under ``nearest_copy`` a path whose walk already meets ``t`` is left as it
is.  ``provision`` sweeps a workload through it, re-runs the paths the
routed walk still finds over budget (two rounds), and then drops, object
by object, each replica whose removal keeps every path of the workload
within budget.  Storage cost f(v) = 1 for every object.
"""
from __future__ import annotations

import itertools

import numpy as np

from bench.reference.walk import walk_latencies

REVALIDATE_ROUNDS = 2


def _groups(path, shard):
    groups = [[path[0]]]
    for v in path[1:]:
        if shard[v] == shard[groups[-1][-1]]:
            groups[-1].append(v)
        else:
            groups.append([v])
    return groups


def update_path(mask, shard, path, t: int, policy: str,
                first: bool = False) -> list[tuple[int, int]]:
    """UPDATE one path in place; returns the (object, server) pairs added.

    ``first`` takes the first choice instead of the cheapest: a planted
    fault, for reading the limit of ``replica_excess``."""
    if not path:
        return []
    groups = _groups(path, shard)
    h = len(groups) - 1
    if h <= t:
        return []
    if policy != "home_first":
        lat = walk_latencies(
            np.asarray([path]), np.asarray([len(path)]), mask, shard, policy
        )[0]
        if lat <= t:
            return []
    gsrv = [int(shard[g[0]]) for g in groups]
    best = None
    for subset in itertools.combinations(range(1, h + 1), t):
        kept = {0, *subset}
        added: list[tuple[int, int]] = []
        seen: set[tuple[int, int]] = set()
        for i in range(1, h + 1):
            if i in kept:
                continue
            j = max(x for x in kept if x < i)
            for v in groups[i]:
                for k in range(j, i):
                    s = gsrv[k]
                    if mask[v, s] or (v, s) in seen:
                        continue
                    seen.add((v, s))
                    added.append((v, s))
        if best is None or len(added) < len(best):
            best = added
        if first:
            break
    for v, s in best:
        mask[v, s] = True
    return best


def sweep(mask, shard, objects, lengths, budgets, rows, policy: str,
          first: bool = False) -> None:
    for i in rows:
        n = int(lengths[i])
        update_path(mask, shard, objects[i, :n].tolist(), int(budgets[i]),
                    policy, first)


def over_budget(mask, shard, objects, lengths, budgets, policy, rows=None):
    """Rows whose walk exceeds their budget."""
    if rows is None:
        rows = np.arange(len(objects))
    h = walk_latencies(objects[rows], lengths[rows], mask, shard, policy)
    return rows[h > budgets[rows]]


def repair(mask, shard, objects, lengths, budgets, policy: str,
           first: bool = False) -> None:
    """Sweep, then re-run what the routed walk still finds over budget."""
    sweep(mask, shard, objects, lengths, budgets,
          range(len(objects)), policy, first)
    if policy == "home_first":
        return
    for _ in range(REVALIDATE_ROUNDS):
        viol = over_budget(mask, shard, objects, lengths, budgets, policy)
        if not len(viol):
            return
        sweep(mask, shard, objects, lengths, budgets, viol, policy, first)


def prune(mask, shard, objects, lengths, budgets, policy: str) -> int:
    """Drop each replica (object-major order) whose removal keeps every
    path through its object within budget; returns the number dropped."""
    if len(over_budget(mask, shard, objects, lengths, budgets, policy)):
        return 0
    rows_of: dict[int, list[int]] = {}
    for i in range(len(objects)):
        for v in set(objects[i, : int(lengths[i])].tolist()):
            rows_of.setdefault(v, []).append(i)
    repl = mask.copy()
    repl[np.arange(len(shard)), shard] = False
    dropped = 0
    for v, s in zip(*np.nonzero(repl)):
        rows = np.asarray(rows_of.get(int(v), []), np.int64)
        mask[v, s] = False
        if len(rows) and len(over_budget(mask, shard, objects, lengths,
                                         budgets, policy, rows)):
            mask[v, s] = True
        else:
            dropped += 1
    return dropped


def provision(objects, lengths, shard, n_servers: int, budgets,
              policy: str, do_prune: bool = True,
              first: bool = False) -> np.ndarray:
    """A from-scratch scheme for the workload: bool [n_objects, n_servers]."""
    shard = np.asarray(shard, np.int64)
    mask = np.zeros((len(shard), n_servers), bool)
    mask[np.arange(len(shard)), shard] = True
    objects = np.asarray(objects, np.int64)
    lengths = np.asarray(lengths, np.int64)
    budgets = np.broadcast_to(np.asarray(budgets, np.int64), lengths.shape)
    repair(mask, shard, objects, lengths, budgets, policy, first)
    if do_prune and policy != "home_first":
        prune(mask, shard, objects, lengths, budgets, policy)
    return mask
