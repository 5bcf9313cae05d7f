"""LatencyEngine: one backend-dispatched evaluation core for h(p, r, rho).

The paper's whole algorithm family reduces to evaluating the latency of
many paths against an evolving replication scheme; this class is the single
implementation every consumer (greedy UPDATE driver, exact reference,
baselines, the distsys executor, the workload analyzer, and all
benchmarks) routes through.

  engine = LatencyEngine(scheme, backend="pallas")
  h  = engine.path_latencies(pathset)        # int32 [n_paths]
  lq = engine.query_latencies(pathset, h)    # int32 [n_queries]
  ok = engine.is_feasible(pathset, t, path_lats=h)
  dc = engine.margin_costs(cand_objs, cand_srvs, f)   # vs device snapshot
  engine.add_replicas(objs, srvs)            # on-device scatter-OR

State model: by default (``resident=True``) the scheme lives on device as
a :class:`~repro.engine.packed.PackedScheme` — one packed upload at
construction, incremental scatter-OR updates afterwards, and chunked
evaluation streams only the int32 path chunks (double-buffered, see
``streaming``).  ``resident=False`` reproduces the seed implementation's
transfer profile (bool mask re-uploaded every call) and exists for the
perf benchmarks and regression comparisons.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.engine import backends
from repro.engine.packed import PackedScheme, pack_bool_mask
from repro.engine.routing import resolve_policy
from repro.engine.streaming import stream_chunks, to_device, to_host

DEFAULT_CHUNK = 8192


@dataclasses.dataclass
class RawScheme:
    """Lightweight mask + shard scheme (the engine's minimal input contract).

    Anything with ``.mask`` (bool [n, S]) and ``.shard`` (int32 [n]) can
    back a :class:`LatencyEngine`; this is the canonical minimal carrier —
    used by :meth:`LatencyEngine.from_arrays` and anywhere a full
    ``repro.core.ReplicationScheme`` (with its storage accounting) would be
    overkill.  Mutable on purpose: ``add_replicas`` flips its mask bits in
    place like any other scheme.
    """

    mask: np.ndarray
    shard: np.ndarray

    def __post_init__(self):
        self.mask = np.asarray(self.mask, bool)
        self.shard = np.asarray(self.shard, np.int32)
        assert self.mask.ndim == 2
        assert self.shard.shape == (self.mask.shape[0],)


def _budget_vector(t, n_queries: int) -> np.ndarray:
    """int | per-query array | SLOSpec (duck-typed ``.t_q``) -> int32 [nq].

    Duck typing keeps ``repro.engine`` free of ``repro.core`` imports
    (core sits above the engine in the layering).
    """
    t = getattr(t, "t_q", t)
    return np.broadcast_to(
        np.asarray(t, np.int32), (n_queries,)
    )


class DevicePaths:
    """A PathSet pinned to the device (uploaded once, reused per call)."""

    def __init__(self, pathset):
        self.n_paths = pathset.n_paths
        self.n_queries = pathset.n_queries
        self.query_ids = np.asarray(pathset.query_ids)
        self.objects = to_device(np.asarray(pathset.objects, np.int32))
        self.lengths = to_device(np.asarray(pathset.lengths, np.int32))


class LatencyEngine:
    """Backend-dispatched latency evaluation over a replication scheme.

    Args:
      scheme: anything with ``.mask`` (bool [n, S]) and ``.shard``
        (int [n]) — typically ``repro.core.ReplicationScheme`` — or None
        when ``packed`` is given directly.
      backend: "reference" | "jnp" | "pallas".
      chunk: paths per evaluation chunk (streaming granularity).
      block: Pallas path-block (lane) size.
      resident: keep the packed scheme device-resident (default).  When
        False the engine re-uploads the unpacked bool mask on every
        ``path_latencies`` call, mimicking the seed implementation.
    """

    def __init__(
        self,
        scheme=None,
        *,
        packed: PackedScheme | None = None,
        backend: str = "jnp",
        chunk: int = DEFAULT_CHUNK,
        block: int = 128,
        resident: bool = True,
    ):
        if backend not in backends.BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; use {backends.BACKENDS}")
        if scheme is None and packed is None:
            raise ValueError("need a scheme or a PackedScheme")
        self.backend = backend
        self.chunk = int(chunk)
        self.block = int(block)
        self.resident = resident or packed is not None
        self.scheme = scheme
        self.packed: PackedScheme | None = packed
        if self.packed is None and self.resident:
            self.packed = PackedScheme.from_mask(scheme.mask, scheme.shard)
        # lazy incremental dirty-set evaluation plane (engine.incremental)
        self._inc = None

    # -- classmethods -----------------------------------------------------
    @classmethod
    def from_arrays(cls, mask: np.ndarray, shard: np.ndarray, **kw) -> "LatencyEngine":
        return cls(RawScheme(mask, shard), **kw)

    # -- state ------------------------------------------------------------
    @property
    def n_servers(self) -> int:
        if self.packed is not None:
            return self.packed.n_servers
        return self.scheme.mask.shape[1]

    def host_mask(self) -> np.ndarray:
        """Current bool mask on host (readback when device-resident)."""
        if self.packed is not None:
            return self.packed.unpack()
        return np.asarray(self.scheme.mask, bool)

    def host_shard(self) -> np.ndarray:
        if self.packed is not None:
            return to_host(self.packed.shard)
        return np.asarray(self.scheme.shard, np.int32)

    @property
    def incremental(self):
        """The engine's :class:`~repro.engine.incremental.IncrementalEval`.

        Created on first use; scheme mutations routed through this engine
        (:meth:`add_replicas` / :meth:`remove_replicas` /
        :meth:`note_changed` / :meth:`refresh`) keep it exact.
        """
        if self._inc is None:
            from repro.engine.incremental import IncrementalEval  # lazy

            self._inc = IncrementalEval(self)
        return self._inc

    def note_changed(self, objects) -> None:
        """Invalidate cached incremental latencies of paths touching
        ``objects``.

        :meth:`add_replicas` / :meth:`remove_replicas` call this
        automatically; callers that mutate ``packed.words`` directly
        (the fused greedy UPDATE jits) must call it themselves with the
        objects they touched — a superset is safe, a miss is not.
        """
        if self._inc is not None:
            self._inc.invalidate_objects(objects)

    def refresh(self, objects=None) -> None:
        """Re-pack after the host scheme's mask was mutated directly.

        ``objects`` — when the caller knows the exact set of objects whose
        replica rows changed (a §5.4 drain's dirty set) — invalidates only
        the cached latencies of paths touching them, keeping the rest of
        the incremental cache warm; without it every cached vector is
        dropped (the safe call for layout changes like scale-out).
        """
        if self.scheme is not None and self.resident:
            self.packed = PackedScheme.from_mask(self.scheme.mask, self.scheme.shard)
        if self._inc is not None:
            if objects is None:
                # no delta to reason about: drop every cached latency vector
                self._inc.invalidate_all()
            else:
                self._inc.invalidate_objects(objects)

    def add_replicas(self, objects, servers) -> None:
        """Monotone additions, applied on device (and to the host scheme).

        Pairs with a negative object or server are ignored, matching the
        packed scatter-OR semantics (negative indices must not wrap).
        """
        obj = np.asarray(objects)
        srv = np.asarray(servers)
        ok = (obj >= 0) & (srv >= 0)
        obj, srv = obj[ok], srv[ok]
        if obj.size == 0:
            return
        if self.packed is not None:
            self.packed.add(obj, srv)
        if self.scheme is not None:
            self.scheme.mask[obj, srv] = True
        self.note_changed(obj)

    def remove_replicas(self, objects, servers) -> None:
        """Drop replicas, applied on device (and to the host scheme).

        The inverse of :meth:`add_replicas` (same negative-pair masking),
        used by the policy prune sweep.  Removals are not monotone: the
        caller owns the feasibility re-check.
        """
        obj = np.asarray(objects)
        srv = np.asarray(servers)
        ok = (obj >= 0) & (srv >= 0)
        obj, srv = obj[ok], srv[ok]
        if obj.size == 0:
            return
        if self.packed is not None:
            self.packed.remove(obj, srv)
        if self.scheme is not None:
            self.scheme.mask[obj, srv] = False
        self.note_changed(obj)

    def prepare(self, pathset) -> DevicePaths:
        """Pin a PathSet on device for repeated evaluation (one upload)."""
        return DevicePaths(pathset)

    def to_scheme(self):
        from repro.core.replication import ReplicationScheme  # lazy: no cycle

        return ReplicationScheme(self.host_mask(), self.host_shard())

    # -- evaluation -------------------------------------------------------
    def path_latencies(
        self,
        pathset,
        chunk: int | None = None,
        policy=None,
        load: np.ndarray | None = None,
        incremental: bool = False,
    ) -> np.ndarray:
        """h(p, r, rho) per path: #distributed traversals (Def 4.2).

        ``policy`` (str | ``RoutingPolicy``; default ``home_first``)
        scores the walk under a hop-routing policy: ``home_first`` is the
        historical Eqn 1 walk (bit-identical to calling without a
        policy); ``nearest_copy``/``queue_aware`` pick remote-hop targets
        from the replica holders (``load`` ranks holders for the
        latter).  All three backends implement every policy.

        ``incremental=True`` routes through the engine's persistent
        per-path latency cache (:attr:`incremental`): the first call for
        a PathSet evaluates fully, later calls re-walk only the paths
        whose latency a scheme delta since then could have changed — the
        exact dirty set of the object->path index.  Bit-identical to
        ``incremental=False`` as long as every scheme mutation is routed
        through the engine (or reported via :meth:`note_changed`).
        """
        pol = resolve_policy(policy)
        if pathset.n_paths == 0:
            return np.zeros((0,), dtype=np.int32)
        if incremental and not isinstance(pathset, DevicePaths):
            return self.incremental.path_latencies(
                pathset, policy=pol, load=load
            )
        if self.backend == "reference":
            if pol.name == "home_first":
                return backends.reference_eval(
                    np.asarray(pathset.objects),
                    np.asarray(pathset.lengths),
                    self.host_mask(),
                    self.host_shard(),
                )
            from repro.core.reference import (  # lazy: no cycle
                routed_path_latencies_reference,
            )

            return routed_path_latencies_reference(
                np.asarray(pathset.objects),
                np.asarray(pathset.lengths),
                self.host_mask(),
                self.host_shard(),
                policy=pol,
                load=load,
            )
        chunk = int(chunk or self.chunk)
        if pol.name == "home_first":
            compute = (
                self._eval_chunk_resident
                if self.resident
                else self._make_nonresident_compute()
            )
        else:
            compute = self._make_policy_compute(pol, load)
        if isinstance(pathset, DevicePaths):
            out = compute(pathset.objects, pathset.lengths)
            return to_host(out)[: pathset.n_paths].astype(np.int32)
        n = pathset.n_paths
        outs = stream_chunks(
            [np.asarray(pathset.objects, np.int32), np.asarray(pathset.lengths, np.int32)],
            n,
            chunk,
            compute,
            pad_values=[-1, 0],
            align=self.block,
        )
        host = [to_host(o) for o in outs]
        return np.concatenate(host, axis=0)[:n].astype(np.int32)

    def _eval_chunk_resident(self, objects, lengths):
        if self.backend == "pallas":
            return backends.pallas_eval(
                objects, lengths, self.packed.words, self.packed.shard,
                block=self.block,
            )
        return backends.words_scan(
            objects, lengths, self.packed.words, self.packed.shard
        )

    def _make_nonresident_compute(self):
        mask_host = np.asarray(self.scheme.mask, bool)
        shard_host = np.asarray(self.scheme.shard, np.int32)
        if self.backend == "pallas":
            words_host = np.concatenate(
                [pack_bool_mask(mask_host),
                 np.zeros((1, (mask_host.shape[1] + 31) // 32), np.uint32)],
                axis=0,
            )

            def compute(objects, lengths):
                return backends.pallas_eval(
                    objects, lengths, to_device(words_host),
                    to_device(shard_host), block=self.block,
                )

            return compute

        def compute(objects, lengths):
            return backends.bool_scan(
                objects, lengths, to_device(mask_host), to_device(shard_host)
            )

        return compute

    def _device_words(self):
        """(words, shard) on device — packed view of the current scheme.

        Resident engines reuse the live ``PackedScheme``; non-resident
        ones pack the host mask per call (the legacy transfer profile).
        """
        if self.packed is not None:
            return self.packed.words, self.packed.shard
        mask_host = np.asarray(self.scheme.mask, bool)
        words_host = np.concatenate(
            [pack_bool_mask(mask_host),
             np.zeros((1, (mask_host.shape[1] + 31) // 32), np.uint32)],
            axis=0,
        )
        return to_device(words_host), to_device(
            np.asarray(self.scheme.shard, np.int32)
        )

    def _make_policy_compute(self, pol, load):
        """Chunk-compute closure for a non-home-first routing policy."""
        words, shard = self._device_words()
        if self.backend == "pallas":

            def compute(objects, lengths):
                return backends.pallas_routed_eval(
                    objects, lengths, words, shard, pol, load,
                    block=self.block,
                )

            return compute

        def compute(objects, lengths):
            return backends.routed_counts(
                objects, lengths, words, shard, pol, load
            )

        return compute

    def access_trace(
        self,
        pathset,
        start: np.ndarray | None = None,
        policy=None,
        load: np.ndarray | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Policy-routed access walk against the engine's scheme.

        Remote hops target the object's home under ``home_first`` (the
        historical walk, bit-identical), or the policy's holder pick
        (``nearest_copy``/``queue_aware``; ``load`` = per-server queue
        depths).  ``start`` optionally overrides the per-path start
        server.  Returns host arrays (servers int32 [P, L], local bool
        [P, L]) — the trace the distsys executor and the serving
        simulator decorate with their latency models.
        """
        pol = resolve_policy(policy)
        # a prepare()d DevicePaths reuses its pinned device arrays: the
        # batched serving plane re-traces the same workload under many
        # start/policy variants, and re-uploading objects/lengths each
        # call would tax exactly the dispatch path batching amortizes
        pinned = isinstance(pathset, DevicePaths)
        if self.backend == "reference":
            from repro.core.reference import routed_trace_reference  # lazy

            return routed_trace_reference(
                np.asarray(pathset.objects, np.int32),
                np.asarray(pathset.lengths, np.int32),
                self.host_mask(), self.host_shard(),
                start=start, policy=pol, load=load,
            )
        words, shard = self._device_words()
        obj_d = (
            pathset.objects if pinned
            else to_device(np.asarray(pathset.objects, np.int32))
        )
        len_d = (
            pathset.lengths if pinned
            else to_device(np.asarray(pathset.lengths, np.int32))
        )
        kw = {}
        if start is not None:
            kw["start"] = to_device(np.asarray(start, np.int32))
        if self.backend == "pallas" and pol.name != "home_first":
            servers, local = backends.pallas_routed_trace(
                obj_d, len_d, words, shard,
                pol, load, block=self.block, **kw,
            )
        else:
            servers, local = backends.access_trace(
                obj_d, len_d, words, shard,
                policy=pol, load=load, **kw,
            )
        return np.asarray(servers), np.asarray(local)

    def query_latencies(self, pathset, path_lats: np.ndarray | None = None) -> np.ndarray:
        """l_Q = max over the query's paths (Def 4.3)."""
        if path_lats is None:
            path_lats = self.path_latencies(pathset)
        nq = pathset.n_queries
        out = np.zeros((nq,), dtype=np.int32)
        np.maximum.at(out, np.asarray(pathset.query_ids), path_lats)
        return out

    def query_slack(
        self,
        pathset,
        t,
        path_lats: np.ndarray | None = None,
        policy=None,
        load: np.ndarray | None = None,
        incremental: bool = False,
    ) -> np.ndarray:
        """t_Q - l_Q per query, computed on device (int32 [n_queries]).

        ``t`` is an int (scalar broadcast), a per-query budget vector, or
        an ``SLOSpec``.  The per-query max and the subtraction run on
        device against the budget vector (``backends.query_slack``); only
        the slack vector crosses back.  Negative entries mark violating
        queries — the serve layer's per-tenant triggers consume this.
        ``policy`` scores the walk under a hop-routing policy
        (``nearest_copy`` is the paper-faithful Eqn 1 reading and yields
        slack >= the ``home_first`` default wherever replicas help).
        ``incremental=True`` sources the path latencies from the
        persistent dirty-set cache (see :meth:`path_latencies`).
        """
        if path_lats is None:
            path_lats = self.path_latencies(
                pathset, policy=policy, load=load, incremental=incremental
            )
        nq = pathset.n_queries
        t_q = _budget_vector(t, nq)
        if nq == 0:
            return np.zeros((0,), np.int32)
        out = backends.query_slack(
            to_device(np.asarray(path_lats, np.int32)),
            to_device(np.asarray(pathset.query_ids, np.int32)),
            to_device(t_q),
        )
        return np.asarray(out)

    def is_feasible(
        self,
        pathset,
        t,
        path_lats: np.ndarray | None = None,
        policy=None,
        load: np.ndarray | None = None,
        incremental: bool = False,
    ) -> bool:
        """All queries within their own t_Q (Def 4.4).

        ``t``: int | per-query vector | ``SLOSpec``.  Reuses precomputed
        ``path_lats`` when given.  ``policy="nearest_copy"`` checks
        feasibility under the paper-faithful any-co-located-replica
        routing, a weaker (tighter-scoring) condition than the
        ``home_first`` default.  ``incremental=True`` sources the path
        latencies from the persistent dirty-set cache.
        """
        return bool(
            np.all(
                self.query_slack(
                    pathset, t, path_lats, policy, load,
                    incremental=incremental,
                )
                >= 0
            )
        )

    def resilient_path_latencies(
        self,
        pathset,
        resilience,
        policy=None,
        load: np.ndarray | None = None,
    ) -> np.ndarray:
        """h per (loss case, path) under ``resilience``: int32 [D, P].

        Row d is the policy walk with loss case d's servers down — their
        holder bits cleared from the packed words and every lost home
        remapped by rotation failover (``repro.engine.resilience``).  A
        path is k-resilient iff every row keeps it within budget; the
        max over rows is the resilient latency the greedy gate enforces.
        All three backends implement the masked re-walk (the ``jnp``
        path batches all D cases into one vmapped dispatch).
        """
        from repro.engine.resilience import (
            case_word_mask,
            failover_shard,
            resolve_resilience,
        )

        res = resolve_resilience(resilience)
        if res is None:
            raise ValueError("resilient_path_latencies needs a resilience spec")
        S = self.n_servers
        cases = res.loss_cases(S)
        P = pathset.n_paths
        if P == 0:
            return np.zeros((len(cases), 0), np.int32)
        pol = resolve_policy(policy)
        shard_host = self.host_shard()
        homes = np.stack([failover_shard(shard_host, c, S) for c in cases])
        if self.backend == "reference":
            from repro.core.reference import (  # lazy: no cycle
                path_latencies_reference,
                routed_path_latencies_reference,
            )

            mask = self.host_mask()
            objects = np.asarray(pathset.objects)
            lengths = np.asarray(pathset.lengths)
            rows = []
            for c, fs in zip(cases, homes):
                m = mask.copy()
                m[:, c] = False
                if pol.name == "home_first":
                    rows.append(path_latencies_reference(objects, lengths, m, fs))
                else:
                    rows.append(routed_path_latencies_reference(
                        objects, lengths, m, fs, policy=pol, load=load
                    ))
            return np.stack(rows).astype(np.int32)
        words, _ = self._device_words()
        W = int(words.shape[1])
        case_masks = np.stack([case_word_mask(c, W) for c in cases])
        out = backends.resilient_counts(
            to_device(np.asarray(pathset.objects, np.int32)),
            to_device(np.asarray(pathset.lengths, np.int32)),
            words,
            to_device(case_masks),
            to_device(homes.astype(np.int32)),
            policy=pol,
            load=load,
            backend=self.backend,
            block=self.block,
        )
        return np.asarray(out).astype(np.int32)

    def is_resilient_feasible(
        self,
        pathset,
        t,
        resilience,
        policy=None,
        load: np.ndarray | None = None,
    ) -> bool:
        """Every query within its t_Q under EVERY loss case (Def 4.4 + k).

        The resilient strengthening of :meth:`is_feasible`: the per-query
        latency is maxed over the query's paths *and* over all loss cases
        of ``resilience`` before the budget comparison.
        """
        h = self.resilient_path_latencies(
            pathset, resilience, policy=policy, load=load
        )
        if h.shape[1] == 0:
            return True
        t_q = _budget_vector(t, pathset.n_queries)
        qids = np.asarray(pathset.query_ids)
        worst = h.max(axis=0)  # [P] max over loss cases
        lq = np.zeros(pathset.n_queries, np.int32)
        np.maximum.at(lq, qids, worst)
        return bool(np.all(lq <= t_q))

    def margin_costs(
        self, objects, servers, f: np.ndarray | None = None
    ) -> np.ndarray:
        """Marginal storage cost of candidate additions vs the snapshot.

        ``objects``/``servers`` are int arrays of identical shape
        ``[..., K]``; negative entries are ignored.  Returns float32
        ``[...]`` — the sum of ``f[v]`` over pairs not already replicated.
        """
        packed = self.packed
        if packed is None:
            packed = PackedScheme.from_mask(self.scheme.mask, self.scheme.shard)
        n = packed.n_objects
        fv = np.ones((n,), np.float32) if f is None else np.asarray(f, np.float32)
        out = backends.margin_cost(
            packed.words,
            to_device(fv),
            to_device(np.asarray(objects, np.int32)),
            to_device(np.asarray(servers, np.int32)),
        )
        return np.asarray(out)
