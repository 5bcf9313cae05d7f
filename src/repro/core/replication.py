"""Replication schemes and the latency/access function (paper §4).

A replication scheme ``r`` maps each object to the set of servers holding a
copy; the original copy placed by the sharding function ``d`` is always
included.  We represent ``r`` as a boolean matrix ``[n_objects, n_servers]``
(uint8 on host, bool in JAX).  Monotone 0->1 updates mirror the paper's
lock-free bit-vector implementation (§6.1); batched scatter-ORs are the
SIMD analogue of their 64-thread races, justified by Thm 5.3.

The *access function* rho (Eqn 1) and the path latency h(p, r, rho)
(Eqn 2) are evaluated by ``repro.engine.LatencyEngine`` — the shared
backend-dispatched core (reference | jnp | pallas) with the packed uint32
bitmask as its device-resident source of truth.  The module-level
functions below are thin conveniences that build a transient engine per
call; stateful consumers (the greedy driver, benchmarks) hold an engine
to keep the scheme device-resident across calls.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.paths import PAD, PathSet
from repro.engine import LatencyEngine, pack_bool_mask


@dataclasses.dataclass
class ReplicationScheme:
    """Boolean replication matrix with storage accounting.

    Attributes:
      mask: bool [n_objects, n_servers]; ``mask[v, s]`` == object v has a copy
        at server s.  Always a superset of the sharding function.
      shard: int32 [n_objects]; the sharding function d (home server).
    """

    mask: np.ndarray
    shard: np.ndarray

    @staticmethod
    def from_sharding(shard: np.ndarray, n_servers: int) -> "ReplicationScheme":
        n = shard.shape[0]
        mask = np.zeros((n, n_servers), dtype=bool)
        mask[np.arange(n), shard] = True
        return ReplicationScheme(mask, shard.astype(np.int32))

    @property
    def n_objects(self) -> int:
        return self.mask.shape[0]

    @property
    def n_servers(self) -> int:
        return self.mask.shape[1]

    def copy(self) -> "ReplicationScheme":
        return ReplicationScheme(self.mask.copy(), self.shard)

    def add(self, objects: np.ndarray, servers: np.ndarray) -> None:
        """Monotone in-place addition of replicas (0->1 flips only)."""
        self.mask[objects, servers] = True

    def replica_count(self) -> int:
        """Number of *replica* copies (total copies minus originals)."""
        return int(self.mask.sum()) - self.n_objects

    def storage_per_server(self, f: np.ndarray | None = None) -> np.ndarray:
        """f_r(s) = sum of f(v) over v with s in r(v) (paper notation)."""
        if f is None:
            return self.mask.sum(axis=0).astype(np.float64)
        return f.astype(np.float64) @ self.mask

    def replication_overhead(self, f: np.ndarray | None = None) -> float:
        """Replicated bytes / original bytes (the paper's Fig 2d/6 metric)."""
        if f is None:
            total = float(self.mask.sum())
            orig = float(self.n_objects)
        else:
            total = float(self.storage_per_server(f).sum())
            orig = float(f.sum())
        return (total - orig) / orig

    def is_feasible(
        self,
        f: np.ndarray | None = None,
        capacity: np.ndarray | float | None = None,
        epsilon: float | None = None,
    ) -> bool:
        """Check storage capacity M_s and the eps load-imbalance constraint."""
        cost = self.storage_per_server(f)
        if capacity is not None:
            cap = np.broadcast_to(np.asarray(capacity, dtype=np.float64), cost.shape)
            if np.any(cost > cap + 1e-9):
                return False
        if epsilon is not None:
            mean = cost.mean()
            if mean > 0 and cost.max() > (1.0 + epsilon) * mean + 1e-9:
                return False
        return True

    def pack(self) -> np.ndarray:
        """Pack to uint32 bit-words [n_objects, ceil(S/32)] (kernel input)."""
        return pack_bool_mask(self.mask)


# ---------------------------------------------------------------------------
# Subpath decomposition (Def 5.1) under the *sharding* function d.
# Alg 2 line 2 enumerates server-local subpaths of p under d (no replicas).
# ---------------------------------------------------------------------------
def subpath_structure(objects: jnp.ndarray, lengths: jnp.ndarray, shard: jnp.ndarray):
    """Segment each path into server-local subpaths under d.

    Args:
      objects: int32 [P, L] padded paths.
      lengths: int32 [P].
      shard:   int32 [n_objects] sharding function.

    Returns:
      home: int32 [P, L]  home server per position (PAD positions -> -1)
      seg:  int32 [P, L]  subpath index per position (0-based)
      h:    int32 [P]     number of distributed traversals under d
                          (= #subpaths - 1)
    """
    P, L = objects.shape
    valid = jnp.arange(L)[None, :] < lengths[:, None]
    safe = jnp.maximum(objects, 0)
    home = jnp.where(valid, shard[safe], -1).astype(jnp.int32)
    prev = jnp.concatenate([jnp.full((P, 1), -2, jnp.int32), home[:, :-1]], axis=1)
    boundary = valid & (jnp.arange(L)[None, :] > 0) & (home != prev)
    seg = jnp.cumsum(boundary.astype(jnp.int32), axis=1)
    seg = jnp.where(valid, seg, -1)
    last = jnp.maximum(lengths - 1, 0)
    h = jnp.take_along_axis(seg, last[:, None], axis=1)[:, 0]
    h = jnp.where(lengths > 0, h, 0)
    return home, seg, h


# ---------------------------------------------------------------------------
# Latency of paths under a replication scheme (Eqns 1-3) — engine-backed.
# ---------------------------------------------------------------------------
def path_latencies(
    pathset: PathSet,
    scheme: ReplicationScheme,
    chunk: int = 8192,
    backend: str = "jnp",
    policy=None,
) -> np.ndarray:
    """h(p, r, rho) for every path: #distributed traversals (Def 4.2).

    Convenience wrapper: builds a transient ``LatencyEngine`` (one packed
    upload) per call.  Hold an engine yourself for repeated evaluation
    against an evolving scheme.  ``policy`` scores the walk under a
    ``repro.engine.routing`` hop policy (default ``home_first``).
    """
    eng = LatencyEngine(scheme, backend=backend, chunk=chunk)
    return eng.path_latencies(pathset, policy=policy)


def query_latencies(
    pathset: PathSet,
    scheme: ReplicationScheme,
    path_lats: np.ndarray | None = None,
) -> np.ndarray:
    """l_Q = max over the query's paths (Def 4.3); int array [n_queries].

    ``path_lats`` lets callers that already hold per-path latencies skip
    the full re-scan.
    """
    if path_lats is None:
        path_lats = path_latencies(pathset, scheme)
    nq = pathset.n_queries
    out = np.zeros((nq,), dtype=np.int32)
    np.maximum.at(out, pathset.query_ids, path_lats)
    return out


def path_latency_reference(path: list[int], mask: np.ndarray, shard: np.ndarray) -> int:
    """Pure-python oracle for a single path (used by tests)."""
    if not path:
        return 0
    server = int(shard[path[0]])
    cost = 0
    for v in path[1:]:
        if mask[v, server]:
            continue  # local replica: stay (Eqn 1 first case)
        server = int(shard[v])  # distributed traversal to the original copy
        cost += 1
    return cost


def query_slacks(
    pathset: PathSet,
    scheme: ReplicationScheme,
    t,
    path_lats: np.ndarray | None = None,
    policy=None,
) -> np.ndarray:
    """Per-query slack t_Q - l_Q (negative = violating its constraint).

    ``t`` is an int (broadcast), a per-query budget vector, or an
    :class:`~repro.core.slo.SLOSpec`.  ``policy`` scores the walk under a
    hop-routing policy (ignored when ``path_lats`` is given).
    Convenience wrapper; stateful consumers use
    ``LatencyEngine.query_slack`` to stay device-resident.
    """
    if path_lats is None:
        path_lats = path_latencies(pathset, scheme, policy=policy)
    lq = query_latencies(pathset, scheme, path_lats=path_lats)
    t_q = getattr(t, "t_q", t)
    return (
        np.broadcast_to(np.asarray(t_q, np.int64), lq.shape) - lq
    ).astype(np.int64)


def is_latency_feasible(
    pathset: PathSet,
    scheme: ReplicationScheme,
    t,
    path_lats: np.ndarray | None = None,
    policy=None,
) -> bool:
    """All queries within their latency constraint t_Q (Def 4.4 constraint 1).

    ``t``: int | per-query vector | :class:`~repro.core.slo.SLOSpec`.
    Pass ``path_lats`` (per-path traversal counts) when already computed —
    the check then skips the full Eqn 1-2 re-scan entirely.  ``policy``
    scores feasibility under a hop-routing policy (``nearest_copy`` /
    ``nearest_copy_dp`` are the paper-faithful tighter readings).
    """
    return bool(
        np.all(
            query_slacks(pathset, scheme, t, path_lats=path_lats, policy=policy)
            >= 0
        )
    )


_PRUNE_GROUP_MAX = 512     # candidates per fused prune dispatch
_PRUNE_ROW_BUCKET = 1024   # affected-row padding quantum (bounds jit shapes)


@functools.partial(
    jax.jit,
    static_argnames=("pol", "backend", "G"),
    donate_argnums=(0,),
)
def _prune_group_step(
    words, gobj, gsrv, robj, rlen, rt, rcand, shard, rank, pol, backend, G
):
    """One fused prune round over an independent candidate group.

    Clears all ``G`` candidate bits at once, re-walks every affected row
    under the policy in the same jit, scatter-maxes per-row violations
    back onto their owning candidate, and restores exactly the infeasible
    candidates' bits — a single dispatch replacing ~3 per candidate.
    Row/candidate padding uses index -1 (violations land in a trash slot,
    restores in the sacrificial row).
    """
    from repro.engine.backends import gate_counts  # lazy: no cycle at import
    from repro.engine.packed import scatter_clear_pairs, scatter_or_pairs

    words = scatter_clear_pairs(words, gobj, gsrv)
    h = gate_counts(robj, rlen, words, shard, pol, rank, backend=backend)
    viol = h > rt  # pad rows: length 0 -> h = 0 <= rt = 0, never violating
    slot = jnp.where(rcand >= 0, rcand, G)
    bad = jnp.zeros((G + 1,), jnp.bool_).at[slot].max(viol)[:G]
    words = scatter_or_pairs(words, jnp.where(bad, gobj, -1), gsrv)
    return words, bad


def _independent_groups(order, vs, affected, n_paths, group_max):
    """Partition prune candidates into serially-equivalent batches.

    Two candidates are independent iff no path touches both objects —
    then neither's keep/drop decision can change what the other's
    affected walks read.  Greedy sweep in the serial (descending-f)
    order with *deferral closure*: once a candidate is deferred, its
    affected rows block every later candidate from joining the current
    group, so no candidate is ever evaluated against a snapshot that
    differs from the serial sweep's.
    """
    remaining = list(order)
    groups = []
    while remaining:
        used = np.zeros(n_paths, bool)
        group: list[int] = []
        deferred: list[int] = []
        for i in remaining:
            rows = affected(int(vs[i]))
            if len(group) < group_max and not used[rows].any():
                group.append(i)
            else:
                deferred.append(i)
            used[rows] = True
        groups.append(group)
        remaining = deferred
    return groups


def prune_scheme_replicas(
    scheme: ReplicationScheme,
    pathset: PathSet,
    t,
    policy="nearest_copy",
    f: np.ndarray | None = None,
    backend: str = "jnp",
    load: np.ndarray | None = None,
    group_max: int = _PRUNE_GROUP_MAX,
) -> tuple[int, float]:
    """Drop replicas a policy-routed walk doesn't need for feasibility.

    The greedy driver provisions against the ``home_first`` walk (every
    remote hop pays the trip to the object's home); when the serving path
    routes hops replica-aware (``nearest_copy`` — the paper-faithful
    "any co-located copy counts" reading of Eqn 1), some of those bytes
    are redundant.  This post-pass visits the scheme's replicas
    (non-originals) largest-``f`` first, tentatively removes each, and
    keeps the removal when the workload stays feasible under ``policy``
    scoring.  Mutates ``scheme`` in place; returns
    ``(n_dropped, bytes_saved)``.

    The feasibility re-check is *incremental*: a walk only reads the
    replica words of its own path's objects, so removing the copy
    (v, s) can only change paths that contain ``v``, and only those
    affected paths are re-walked against their own budgets.

    One greedy sweep, not an optimal set cover — the measured bytes are
    a lower bound on the over-provisioning.

    On a device backend (``jnp`` | ``pallas``) the sweep is batched:
    candidates whose objects never co-occur on any path are independent
    (neither decision changes the rows the other's walks read), so each
    independent group is cleared, re-validated, and selectively restored
    in ONE jit dispatch (``_prune_group_step``) — decision-for-decision
    identical to the serial sweep by the deferral-closure grouping (see
    :func:`_independent_groups`).  ``backend="reference"`` runs the
    serial sweep, one candidate at a time (the oracle has no traceable
    gate).  ``load`` is the forecast per-server load a ``queue_aware``
    policy prices the walks with (ignored by load-blind policies).
    """
    from repro.core.slo import normalize_path_budgets  # local: no cycle
    from repro.engine import backends as _backends
    from repro.engine import to_device, to_host
    from repro.engine.incremental import PathIndex  # lazy: no cycle
    from repro.engine.routing import resolve_policy

    pol = resolve_policy(policy)
    with obs.span("repro.greedy.prune.pack"):
        engine = LatencyEngine(scheme, backend=backend)
        objects = np.asarray(pathset.objects, np.int32)
        lengths = np.asarray(pathset.lengths, np.int32)
        t_path = normalize_path_budgets(t, pathset).astype(np.int64)
        h0 = np.asarray(
            engine.path_latencies(pathset, policy=pol, load=load), np.int64
        )
        if pathset.n_paths == 0 or np.any(h0 > t_path):
            return 0, 0.0
        fv = (
            np.ones(scheme.n_objects, np.float64)
            if f is None
            else np.asarray(f, np.float64)
        )

        # object -> rows of the paths that touch it (built once; same CSR
        # the engine's incremental dirty-set cache uses)
        index = PathIndex(objects, scheme.n_objects)
        affected = index.paths_of

        repl = scheme.mask.copy()
        repl[np.arange(scheme.n_objects), scheme.shard] = False
        vs, ss = np.nonzero(repl)
        order = np.argsort(-fv[vs], kind="stable")

    L = objects.shape[1]

    def reference_step(group):
        """Serial oracle: one candidate, cleared on the host mask."""
        from repro.core.reference import routed_path_latencies_reference

        (i,) = group
        rows = affected(int(vs[i]))
        scheme.mask[vs[i], ss[i]] = False
        h = routed_path_latencies_reference(
            objects[rows], lengths[rows], scheme.mask, scheme.shard,
            policy=pol, load=load,
        )
        bad = bool(np.any(h > t_path[rows]))
        scheme.mask[vs[i], ss[i]] = bad
        return np.array([bad])

    def device_step(group):
        """One jit over the group's candidates and their affected rows."""
        G = group_max  # fixed group shape -> one jit trace
        gobj = np.full(G, -1, np.int32)
        gsrv = np.full(G, -1, np.int32)
        gobj[: len(group)] = vs[group]
        gsrv[: len(group)] = ss[group]
        rows = [affected(int(vs[i])) for i in group]
        R = max(1, sum(len(r) for r in rows))
        Rb = -(-R // _PRUNE_ROW_BUCKET) * _PRUNE_ROW_BUCKET
        robj = np.full((Rb, L), -1, np.int32)
        rlen = np.zeros(Rb, np.int32)
        rt = np.zeros(Rb, np.int32)
        rcand = np.full(Rb, -1, np.int32)
        at = 0
        for c, r in enumerate(rows):
            robj[at : at + len(r)] = objects[r]
            rlen[at : at + len(r)] = lengths[r]
            rt[at : at + len(r)] = t_path[r]
            rcand[at : at + len(r)] = c
            at += len(r)
        engine.packed.words, bad = _prune_group_step(
            engine.packed.words,
            to_device(gobj), to_device(gsrv),
            to_device(robj), to_device(rlen), to_device(rt),
            to_device(rcand),
            engine.packed.shard, rank, pol, backend, G,
        )
        return to_host(bad)[: len(group)]

    with obs.span("repro.greedy.prune.sweep"):
        if backend == "reference":
            groups, step = [[i] for i in order], reference_step
        else:
            groups = _independent_groups(
                order, vs, affected, pathset.n_paths, group_max
            )
            rank = _backends._load_vector(
                load if pol.uses_load else None, engine.packed.words
            )
            step = device_step
        drop = np.zeros(len(vs), bool)
        for group in groups:
            drop[np.asarray(group, np.int64)[~step(group)]] = True
        if obs.enabled():
            obs.REGISTRY.counter("repro.greedy.prune.dispatches").inc(
                len(groups)
            )
            obs.REGISTRY.counter("repro.greedy.prune.candidates").inc(
                len(order)
            )
        gi = order[drop[order]]  # dropped candidates, in serial order
        scheme.mask[vs[gi], ss[gi]] = False
        return len(gi), float(fv[vs[gi]].sum())
