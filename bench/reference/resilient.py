"""k-resilient provisioning: the walk under the loss of servers, and a
sequential greedy that keeps every path within budget through each loss.

A loss case takes the servers of ``k`` fault domains down (by default one
domain per server, so k = 1 is each single server in turn).  Under a case:

* the lost servers' copies are gone: their columns of the mask are
  cleared;
* rotation failover: an object homed on a lost server is homed on the
  next surviving server in the cyclic order home+1, home+2, ... (mod S),
  whatever copies exist;
* a walk (``bench.reference.walk``, under the failover homes) that
  reaches an object with no surviving copy is stranded: it stands on no
  server, so no later object is local until the walk hops to a holder.
  The view gives such objects one extra column, held by nothing else.

``provision`` is the plain sequential greedy of ``bench.reference.greedy``
followed by bounded rounds: each round walks every path under every case;
for each case with paths over budget, the objects of those paths whose
home the case took down and whose failover home holds no copy are given
one there, ``repair`` runs the case's violating paths over the case's
view under the failover homes, and what it added joins the live scheme
before the next case.
"""
from __future__ import annotations

import itertools

import numpy as np

from bench.reference import greedy
from bench.reference.walk import walk_latencies

ROUNDS = 3


def loss_cases(n_servers: int, k: int = 1, domains=None) -> list:
    """The server sets that ``k`` of the fault domains take down, each a
    sorted int64 array; ``domains`` None is one domain per server."""
    doms = [[s] for s in range(n_servers)] if domains is None else domains
    cases = []
    for combo in itertools.combinations(doms, k):
        lost = np.unique(np.concatenate([np.asarray(d, np.int64)
                                         for d in combo]))
        if len(lost) >= n_servers:
            raise ValueError("a loss case takes every server down")
        cases.append(lost)
    return cases


def failover_homes(shard, lost, n_servers: int) -> np.ndarray:
    """Home of each object under the loss of ``lost``, by rotation."""
    alive = np.ones(n_servers, bool)
    alive[np.asarray(lost, np.int64)] = False
    nxt = np.asarray([next((s + o) % n_servers for o in range(n_servers)
                           if alive[(s + o) % n_servers])
                      for s in range(n_servers)], np.int64)
    return nxt[np.asarray(shard, np.int64)]


def loss_view(mask, lost) -> np.ndarray:
    """bool [n, S + 1]: ``mask`` without the lost servers' copies, and a
    last column held by exactly the objects left with no copy."""
    n, S = mask.shape
    view = np.zeros((n, S + 1), bool)
    view[:, :S] = mask
    view[:, np.asarray(lost, np.int64)] = False
    view[:, S] = ~view[:, :S].any(axis=1)
    return view


def loss_latencies(objects, lengths, mask, shard, policy, lost) -> np.ndarray:
    """Distributed traversals per path under the loss of ``lost``."""
    home = failover_homes(shard, lost, mask.shape[1])
    return walk_latencies(objects, lengths, loss_view(mask, lost), home,
                          policy)


def over_t_under_loss(objects, lengths, mask, shard, budgets, policy,
                      cases) -> int:
    """The worst, over the loss cases, of the paths over budget."""
    return max(int((loss_latencies(objects, lengths, mask, shard, policy,
                                   lost) > budgets).sum())
               for lost in cases)


def enforce(mask, shard, objects, lengths, budgets, policy: str, cases,
            first: bool = False) -> int:
    """The bounded repair rounds, in place on ``mask``; returns the
    (case, path) pairs still over budget after the last round."""
    n, S = mask.shape
    homes = [failover_homes(shard, lost, S) for lost in cases]
    for rnd in range(ROUNDS + 1):
        over = [walk_latencies(objects, lengths, loss_view(mask, lost), home,
                               policy) > budgets
                for lost, home in zip(cases, homes)]
        total = int(sum(o.sum() for o in over))
        if total == 0 or rnd == ROUNDS:
            return total
        for lost, home, o in zip(cases, homes, over):
            rows = np.flatnonzero(o)
            if not len(rows):
                continue
            view = loss_view(mask, lost)
            objs = np.unique(objects[rows])
            objs = objs[objs >= 0]
            dead = np.zeros(S, bool)
            dead[lost] = True
            orphans = objs[dead[shard[objs]] & ~view[objs, home[objs]]]
            view[orphans, home[orphans]] = True
            view[orphans, S] = False
            greedy.repair(view, home, objects[rows], lengths[rows],
                          budgets[rows], policy, first)
            mask |= view[:, :S]
    return total


def provision(objects, lengths, shard, n_servers: int, budgets, policy: str,
              do_prune: bool = True, first: bool = False, k: int = 1,
              domains=None) -> np.ndarray:
    """A from-scratch k-resilient scheme: bool [n_objects, n_servers].

    ``first`` takes the first choice in every UPDATE, the plain greedy's
    and the repairs': a planted fault, for reading the limit of
    ``replica_excess``."""
    shard = np.asarray(shard, np.int64)
    objects = np.asarray(objects, np.int64)
    lengths = np.asarray(lengths, np.int64)
    budgets = np.broadcast_to(np.asarray(budgets, np.int64), lengths.shape)
    mask = greedy.provision(objects, lengths, shard, n_servers, budgets,
                            policy, do_prune, first)
    enforce(mask, shard, objects, lengths, budgets, policy,
            loss_cases(n_servers, k, domains), first)
    return mask
