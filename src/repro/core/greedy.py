"""Vectorized greedy latency-bound replication (paper Alg 1 + Alg 2).

TPU/JAX adaptation of the paper's lock-free 64-thread implementation
(§6.1): paths are processed in *batches*; every path in a batch evaluates
its candidate subsets against the same snapshot of the replication scheme,
and all chosen additions are applied with one scatter-OR.  Replica additions
are monotone 0->1 flips, and Thm 5.3 (latency-robustness) guarantees that
additions made concurrently for other paths can never break a bound that an
earlier UPDATE established — the exact argument the paper uses to justify
its lock-free races.  The only effect is a mild over-estimate of candidate
costs inside a batch (same approximation class as the paper's threads),
which can make the result slightly more expensive, never infeasible.

Per batch, for each path we compute
  * the server-local subpath structure under d (Def 5.1),
  * for every candidate retained-set (precomputed C(h, t) tables), the
    upward-replication + latency-robustness additions (Alg 2 lines 11-19)
    as a [positions x subpaths] interval mask,
  * the marginal cost of each candidate against the snapshot,
  * optionally the per-candidate marginal server loads for the capacity /
    balance constraints (Alg 2 line 20),
and apply the argmin candidate's additions.

Paths whose subpath count exceeds the enumeration budget fall back to the
exact sequential implementation (``repro.core.reference``).

Latency constraints are **vector-valued** (paper Def 4.4 is per query):
``t`` may be an int, a per-query vector, or an
:class:`~repro.core.slo.SLOSpec`.  Paths are bucketed by distinct budget
(tightest first) — each budget class gets its own C(h, t) candidate
tables and vectorized/sequential split, and the batch kernel gates
additions on each path's own ``t_q`` — with the scalar case degenerating
to one class, bit-identical to the historical scalar driver.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import combi
from repro.core.paths import PathSet
from repro.core.replication import ReplicationScheme, subpath_structure
from repro.core.reference import update_exact
from repro.engine import LatencyEngine, PackedScheme, to_device
from repro.engine.packed import scatter_or_pairs, test_bits

_INF = jnp.float32(1e30)


def _update_batch_core(
    words: jnp.ndarray,      # uint32 [(n+1), W] — packed scheme, sacrificial row
    objects: jnp.ndarray,    # int32 [B, L]
    lengths: jnp.ndarray,    # int32 [B]
    shard: jnp.ndarray,      # int32 [n]
    f: jnp.ndarray,          # float32 [n]
    tables: jnp.ndarray,     # bool [H+1, C, H+1]
    counts: jnp.ndarray,     # int32 [H+1]
    t: jnp.ndarray,          # int32 [B] per-path latency budgets t_q
    h_routed: jnp.ndarray,   # int32 [B] routed path latency vs the snapshot
    load: jnp.ndarray,       # float32 [S] current storage per server
    capacity: jnp.ndarray,   # float32 [S] (ignored unless check_capacity)
    epsilon: jnp.ndarray,    # float32 scalar
    check_capacity: bool,
    routed_gate: bool,
):
    B, L = objects.shape
    Hp1 = tables.shape[2]
    C = tables.shape[1]
    S = load.shape[0]

    home, seg, h = subpath_structure(objects, lengths, shard)
    valid = seg >= 0
    h_cl = jnp.clip(h, 0, Hp1 - 1)

    # server of each subpath: all positions of a subpath share one home.
    seg_cl = jnp.clip(seg, 0, Hp1 - 1)
    b_idx = jnp.arange(B)[:, None].repeat(L, 1)
    srv = (
        jnp.zeros((B, Hp1), jnp.int32)
        .at[b_idx, seg_cl]
        .max(jnp.where(valid, home + 1, 0))
        - 1
    )  # [B, Hp1]; -1 for absent subpaths

    # first object of each subpath (representative u for the resharding map)
    big = jnp.int32(2**30)
    first_pos = (
        jnp.full((B, Hp1), big, jnp.int32)
        .at[b_idx, seg_cl]
        .min(jnp.where(valid, jnp.arange(L)[None, :], big))
    )
    first_obj = jnp.take_along_axis(
        objects, jnp.clip(first_pos, 0, L - 1), axis=1
    )  # [B, Hp1] (garbage where absent; masked later)

    # candidate tables for each path's h: sel [B, C, Hp1]
    sel = tables[h_cl]
    n_cand = counts[h_cl]  # [B]

    # prev_sel[b, c, k] = largest selected subpath index <= k
    idx = jnp.where(sel, jnp.arange(Hp1)[None, None, :], -1)
    prev_sel = jax.lax.cummax(idx, axis=2)  # [B, C, Hp1]

    # per-position selected-predecessor j(seg_x): gather over k = seg_x
    seg_e = jnp.clip(seg, 0, Hp1 - 1)[:, None, :].repeat(C, 1)  # [B, C, L]
    j_of_x = jnp.take_along_axis(prev_sel, seg_e, axis=2)  # [B, C, L]

    # interval mask: additions (x -> subpath k) iff j(seg_x) <= k < seg_x
    k_r = jnp.arange(Hp1)[None, None, None, :]
    window = (k_r >= j_of_x[..., None]) & (k_r < seg_e[..., None])  # [B,C,L,Hp1]
    window = (
        window
        & valid[:, None, :, None]
        & (h > t)[:, None, None, None]  # each path vs its OWN budget t_q
    )
    if routed_gate:
        # policy-aware pricing: a path the *routed* walk already serves
        # within its budget (h(p, r, rho; policy) <= t_q against the same
        # snapshot the candidates are costed on) buys no replicas at all
        window = window & (h_routed > t)[:, None, None, None]
        skipped = (h > t) & (h_routed <= t)
    else:
        skipped = jnp.zeros_like(t, dtype=jnp.bool_)

    # needed(x, k): no copy of objects[x] at srv[k] yet — a bit-test against
    # the engine's device-resident packed snapshot (snapshot semantics)
    safe_obj = jnp.maximum(objects, 0)
    safe_srv = jnp.maximum(srv, 0)
    present = test_bits(
        words, safe_obj[:, :, None], safe_srv[:, None, :]
    )  # [B, L, Hp1]
    needed = (~present) & (srv[:, None, :] >= 0) & valid[:, :, None]

    fx = f[safe_obj] * valid.astype(jnp.float32)  # [B, L]
    add = window & needed[:, None, :, :]  # [B, C, L, Hp1]
    cost = jnp.einsum("bclk,bl->bc", add.astype(jnp.float32), fx)

    cand_valid = jnp.arange(C)[None, :] < n_cand[:, None]
    cost_m = jnp.where(cand_valid, cost, _INF)

    if check_capacity:
        # marginal load per candidate per server: scatter f over srv[k]
        contrib = jnp.einsum("bclk,bl->bck", add.astype(jnp.float32), fx)
        marg = (
            jnp.zeros((B, C, S + 1), jnp.float32)
            .at[
                jnp.arange(B)[:, None, None],
                jnp.arange(C)[None, :, None],
                jnp.clip(safe_srv, 0, S)[:, None, :],
            ]
            .add(contrib)
        )[..., :S]
        # NOTE: snapshot load; within-batch interactions ignored (lock-free
        # semantics).  Feasibility is re-validated exactly by the driver.
        new_load = load[None, None, :] + marg
        ok_cap = jnp.all(new_load <= capacity[None, None, :] + 1e-6, axis=-1)
        mean = jnp.mean(new_load, axis=-1)
        ok_bal = jnp.max(new_load, axis=-1) <= (1.0 + epsilon) * mean + 1e-6
        cost_m = jnp.where(ok_cap & ok_bal, cost_m, _INF)

    best = jnp.argmin(cost_m, axis=1)  # [B] ties -> lowest index (determinism)
    best_cost = jnp.take_along_axis(cost_m, best[:, None], axis=1)[:, 0]
    no_solution = best_cost >= _INF

    chosen = jnp.take_along_axis(add, best[:, None, None, None], axis=1)[:, 0]
    chosen = chosen & ~no_solution[:, None, None]  # [B, L, Hp1]

    # on-device scatter-OR into the packed words; masked-out writes are
    # routed to the sacrificial row by scatter_or_pairs.
    obj_w = jnp.where(chosen, safe_obj[:, :, None], -1)
    srv_w = jnp.broadcast_to(safe_srv[:, None, :], chosen.shape)
    words = scatter_or_pairs(words, obj_w, srv_w)

    applied_cost = jnp.where(no_solution, 0.0, best_cost)
    # Maintain the per-server load incrementally: every applied (x, k)
    # addition contributes f(v_x) to server srv[k].  NOTE this ignores
    # within-batch duplicate (v, s) pairs across different paths (lock-free
    # snapshot semantics) — the driver recomputes the exact load from the
    # mask whenever capacity checking is enabled.
    new_load = load + jnp.einsum(
        "blk,bl,bks->s",
        chosen.astype(jnp.float32),
        fx,
        jax.nn.one_hot(jnp.clip(safe_srv, 0, S - 1), S, dtype=jnp.float32)
        * (srv >= 0).astype(jnp.float32)[..., None],
    )
    return words, applied_cost, no_solution, chosen, first_obj, srv, new_load, skipped


# Back-compat separate-dispatch entry point: the PR-5 pipeline (gate as its
# own host-driven dispatch per batch, stats read back per batch).  The fused
# driver path below replaces it; kept as the benchmark baseline + parity
# anchor.
_update_batch = functools.partial(
    jax.jit,
    static_argnames=("check_capacity", "routed_gate"),
    donate_argnums=(0,),
)(_update_batch_core)


def _first_obj_of_subpaths(objects, lengths, shard, Hp1):
    """[B, Hp1] first object of each subpath (resharding-map representative);
    same ops as the core (garbage where the subpath is absent)."""
    B, L = objects.shape
    _, seg, _ = subpath_structure(objects, lengths, shard)
    valid = seg >= 0
    seg_cl = jnp.clip(seg, 0, Hp1 - 1)
    b_idx = jnp.arange(B)[:, None].repeat(L, 1)
    big = jnp.int32(2**30)
    first_pos = (
        jnp.full((B, Hp1), big, jnp.int32)
        .at[b_idx, seg_cl]
        .min(jnp.where(valid, jnp.arange(L)[None, :], big))
    )
    return jnp.take_along_axis(objects, jnp.clip(first_pos, 0, L - 1), axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("check_capacity", "pol", "use_pallas", "mesh"),
    donate_argnums=(0, 1),
)
def _fused_update_batch(
    words: jnp.ndarray,      # uint32 [(n+1), W] — donated packed snapshot
    acc: jnp.ndarray,        # float32 [3] — donated [cost, failed, skipped] sums
    objects: jnp.ndarray,    # int32 [B, L]
    lengths: jnp.ndarray,    # int32 [B]
    shard: jnp.ndarray,      # int32 [n]
    f: jnp.ndarray,          # float32 [n]
    tables: jnp.ndarray,     # bool [H+1, C, H+1]
    counts: jnp.ndarray,     # int32 [H+1]
    t: jnp.ndarray,          # int32 [B]
    rank: jnp.ndarray,       # float32 [W*32] gate holder-rank (queue load)
    load: jnp.ndarray,       # float32 [S]
    capacity: jnp.ndarray,   # float32 [S]
    epsilon: jnp.ndarray,    # float32 scalar
    check_capacity: bool,
    pol,                     # resolved non-home-first policy or None (static)
    use_pallas: bool,
    mesh=None,               # path-axis Mesh of a sharded batch (static)
):
    """One *fused* UPDATE round: gate + candidate scoring + bit-test +
    scatter-OR in a single dispatch, batch statistics reduced on device
    into ``acc`` (read back once per budget class, not once per batch).

    The routed gate h(p, r, rho; policy) is computed *inside* this jit via
    ``backends.gate_counts`` against the very words snapshot the
    candidates are priced on — no host round trip between gate and UPDATE.
    With ``use_pallas`` the whole round runs as the
    ``kernels.provision_update`` megakernel (capacity checking falls back
    to the jnp core: the marginal-load einsum needs the full [B, C, S]
    plane the lane kernel deliberately never materializes).  With a
    ``mesh`` the megakernel runs once per device on its slice of the
    batch (a Mosaic kernel cannot be partitioned by GSPMD); the
    scatter-OR stays in GSPMD.

    Pad rows (length 0, t 0) are inert in every statistic — h = 0 means
    the empty window costs 0 and C(0, t)'s first candidate accepts — so
    ``acc`` sums the whole padded batch without slicing.
    """
    from repro.engine.backends import gate_counts  # lazy: no cycle at import

    if use_pallas and not check_capacity:
        from repro.kernels import interpret_pallas
        from repro.kernels.provision_update import fused_update_pallas

        words, costs, failed, chosen, srv, skipped = fused_update_pallas(
            words, objects, lengths, shard, f, tables, counts, t, rank,
            pol=pol, interpret=interpret_pallas(), mesh=mesh,
        )
        first_obj = _first_obj_of_subpaths(
            objects, lengths, shard, tables.shape[2]
        )
        new_load = load
    else:
        if pol is None:
            h_routed = jnp.zeros_like(t)
        else:
            def gate(objects, lengths, words, shard, rank):
                return gate_counts(
                    objects, lengths, words, shard, pol, rank,
                    backend="pallas" if use_pallas else "jnp",
                )

            if use_pallas and mesh is not None:
                from repro.engine.sharding import map_paths

                gate = map_paths(gate, mesh, (True, True, False, False, False))
            h_routed = gate(objects, lengths, words, shard, rank)
        (
            words, costs, failed, chosen, first_obj, srv, new_load, skipped
        ) = _update_batch_core(
            words, objects, lengths, shard, f, tables, counts, t, h_routed,
            load, capacity, epsilon, check_capacity, pol is not None,
        )
    acc = acc + jnp.stack(
        [
            jnp.sum(costs),
            jnp.sum(failed.astype(jnp.float32)),
            jnp.sum(skipped.astype(jnp.float32)),
        ]
    )
    return words, acc, new_load, chosen, first_obj, srv


@dataclasses.dataclass
class GreedyStats:
    total_cost: float = 0.0
    failed_paths: int = 0
    paths_processed: int = 0
    fallback_paths: int = 0
    replicas: int = 0
    runtime_s: float = 0.0
    rm: list | None = None
    # paths the routed walk already served within budget (policy-aware
    # greedy only): structurally infeasible under d, zero replicas bought
    routed_skips: int = 0
    # replicas dropped by the driver's final same-policy prune sweep
    # (policy-aware from-scratch runs with policy_prune=True)
    pruned_replicas: int = 0
    # paths still over budget under the routed policy after the bounded
    # revalidation rounds (receding-horizon pathology the rounds could
    # not repair) — 0 means the returned scheme is routed-feasible for
    # every path the driver processed
    routed_violations: int = 0
    # streamed ingestion (replicate_stream): largest number of paths ever
    # host-resident at once — the residency contract the provisioning-scale
    # benchmark asserts stays below the total path count
    peak_resident_paths: int = 0
    # streamed ingestion: host seconds of chunk materialization hidden
    # behind in-flight device compute (the double-buffer pipeline's win)
    ingest_overlap_s: float = 0.0
    # candidate-table residency: the largest host-resident block of
    # C(h, t) selection rows ever built at once, and the total candidate
    # rows shipped to device.  When a budget class's table would exceed
    # ``_TABLE_STREAM_ROWS`` rows the construction streams through
    # bounded chunks, so peak stays at the chunk size while total grows
    # with C(H, t) — the residency contract replicate_stream surfaces in
    # its StreamStats
    table_peak_rows: int = 0
    table_total_rows: int = 0
    # per-budget-class provisioning telemetry (obs-gated; None when the
    # telemetry plane is disabled): dicts of {budget, n_vec, n_seq,
    # n_candidates, routed_skips} in processing order
    timeline: list | None = None
    # dirty-scoped revalidation: path rows the bounded routed-revalidation
    # rounds did NOT have to re-walk (sum over rounds of
    # n_paths - |dirty set|); 0 when revalidation never ran or fell back
    # to full re-evaluation
    revalidate_rows_saved: int = 0
    # k-resilience enforcement (replicate_workload(resilience=...)):
    # (loss case, path) pairs still over budget after the bounded repair
    # rounds — 0 means the returned scheme survives every loss case —
    # and the number of masked repair rounds that actually ran
    resilient_violations: int = 0
    resilience_rounds: int = 0


class DeviceStatsAcc:
    """Deferred device-side stat accumulation across UPDATE passes.

    The fused UPDATE accumulates (cost, failed, skipped) in a device
    f32[3]; reading it back per class blocks dispatch and breaks the
    streamed-ingestion pipeline.  Holding the accumulator here instead
    carries it across :func:`replicate_delta` calls — chunk ``i + 1``'s
    host work proceeds while chunk ``i`` still computes — and
    :meth:`drain` performs the one blocking readback at stream end.
    While deferred, per-chunk stats report these components as 0; the
    caller adds the drained totals once.
    """

    def __init__(self):
        self.acc = None

    def drain(self, stats: "GreedyStats") -> None:
        """One blocking readback; folds the totals into ``stats``."""
        if self.acc is None:
            return
        a = np.asarray(self.acc)
        stats.total_cost += float(a[0])
        stats.failed_paths += int(a[1])
        stats.routed_skips += int(a[2])
        self.acc = None
        if obs.enabled():
            obs.REGISTRY.counter("repro.greedy.stat_readbacks").inc()


def _obs_record_class(stats, b, n_vec, n_seq, counts, n_skip) -> None:
    """Per-budget-class provisioning telemetry (no-op when obs is off)."""
    if not obs.enabled():
        return
    n_cand = int(np.asarray(counts).sum()) if counts is not None else 0
    if stats.timeline is None:
        stats.timeline = []
    stats.timeline.append({
        "budget": int(b),
        "n_vec": int(n_vec),
        "n_seq": int(n_seq),
        "n_candidates": n_cand,
        "routed_skips": int(n_skip),
    })
    reg = obs.REGISTRY
    reg.counter("repro.greedy.classes").inc()
    reg.counter("repro.greedy.vec_paths").inc(n_vec)
    reg.counter("repro.greedy.seq_paths").inc(n_seq)
    reg.counter("repro.greedy.candidates").inc(n_cand)
    reg.counter("repro.greedy.routed_skips").inc(n_skip)


def _run_update_batches(
    packed: PackedScheme,
    vec_objects: np.ndarray,
    vec_lengths: np.ndarray,
    shard_j,
    f_arr: np.ndarray,
    f_j,
    tables,
    counts,
    t_vec: np.ndarray,
    load,
    cap_j,
    eps_j,
    check_capacity: bool,
    batch_size: int,
    stats: GreedyStats,
    track_rm: bool,
    collect_additions: bool = False,
    routed_fn=None,
    fused: bool = False,
    pol=None,
    rank=None,
    use_pallas: bool = False,
    mesh=None,
    acc_holder: DeviceStatsAcc | None = None,
):
    """The batched UPDATE loop over vectorizable paths (shared by the
    from-scratch driver and the incremental delta driver).

    ``t_vec`` is the int32 per-path budget vector (one entry per row of
    ``vec_objects``); the candidate ``tables`` must have been enumerated
    for these budgets (one budget class per call — see the drivers).

    ``routed_fn`` (policy-aware greedy, separate-dispatch path) maps a
    host (objects, lengths) batch to its routed path latencies against
    the *current* packed snapshot; paths within budget under the routed
    walk are gated out of the UPDATE (they buy nothing), re-checked per
    batch so mid-class additions keep shrinking the bill.

    ``fused`` replaces the per-batch (host gate dispatch -> UPDATE
    dispatch -> three blocking stat readbacks) round trip with one
    ``_fused_update_batch`` step per batch: the gate runs inside the same
    jit (``pol`` + ``rank``), stats accumulate in a device vector read
    once at the end, and ``use_pallas`` lowers the round to the
    ``kernels.provision_update`` megakernel.  ``mesh`` (fused only)
    uploads every batch path-sharded across its devices.  ``acc_holder``
    defers even that one end-of-call readback: the device stat vector is
    carried in the holder across calls (streamed ingestion) and drained
    once by the caller — the stat components stay 0 in ``stats`` until
    then.

    Mutates ``packed`` (donated words) and ``stats``; returns the final
    device load and, when ``collect_additions``, the applied (object,
    server) pairs as two int64 arrays.
    """
    add_obj: list[np.ndarray] = []
    add_srv: list[np.ndarray] = []
    nb = len(vec_objects)
    put = to_device
    if fused:
        if acc_holder is not None and acc_holder.acc is not None:
            acc = acc_holder.acc
        else:
            acc = jnp.zeros((3,), jnp.float32)
        if rank is None:
            rank = jnp.zeros((packed.words.shape[1] * 32,), jnp.float32)
    if mesh is not None:
        from repro.engine import sharding as _sharding

        # the words and load are replicated on the mesh for this loop
        # only: the host-driven gate, revalidation and prune outside it
        # run Pallas kernels on one device (GSPMD cannot partition a
        # Mosaic kernel), and a later budget class arrives with both
        # back on one device
        put = _sharding.batch_put(mesh)
        packed.words = _sharding.replicate(packed.words, mesh)
        rank = _sharding.replicate(rank, mesh)
        load = _sharding.replicate(load, mesh)
        acc = _sharding.replicate(acc, mesh)
    for i in range(0, nb, batch_size):
        o = vec_objects[i : i + batch_size]
        l = vec_lengths[i : i + batch_size]
        tq = t_vec[i : i + batch_size]
        # payload = the real rows; pad rows added below cross the bus too
        # but are booked as TRANSFER.padded_bytes, not workload data
        pb_o, pb_l, pb_t = o.nbytes, l.nbytes, tq.nbytes
        if o.shape[0] < batch_size:  # pad batch to a fixed shape
            padn = batch_size - o.shape[0]
            o = np.concatenate([o, np.full((padn, o.shape[1]), -1, np.int32)])
            l = np.concatenate([l, np.zeros((padn,), np.int32)])
            tq = np.concatenate([tq, np.zeros((padn,), np.int32)])
        k = min(batch_size, nb - i)
        if fused:
            packed.words, acc, load, chosen, first_obj, srv = _fused_update_batch(
                packed.words,
                acc,
                put(o, payload_bytes=pb_o),
                put(l, payload_bytes=pb_l),
                shard_j,
                f_j,
                tables,
                counts,
                put(tq, payload_bytes=pb_t),
                rank,
                load,
                cap_j,
                eps_j,
                check_capacity,
                pol,
                use_pallas,
                mesh,
            )
        else:
            if routed_fn is not None:
                # routed latency against the snapshot the batch prices on
                h_rt = np.asarray(routed_fn(o, l), np.int32)
            else:
                h_rt = np.zeros_like(tq)
            packed.words, costs, failed, chosen, first_obj, srv, load, skipped = _update_batch(
                packed.words,
                to_device(o, payload_bytes=pb_o),
                to_device(l, payload_bytes=pb_l),
                shard_j,
                f_j,
                tables,
                counts,
                to_device(tq, payload_bytes=pb_t),
                to_device(h_rt, payload_bytes=h_rt[:k].nbytes),
                load,
                cap_j,
                eps_j,
                check_capacity,
                routed_fn is not None,
            )
            stats.total_cost += float(np.asarray(costs)[:k].sum())
            stats.failed_paths += int(np.asarray(failed)[:k].sum())
            stats.routed_skips += int(np.asarray(skipped)[:k].sum())
        if check_capacity:
            # exact load from the packed words, computed on device (the
            # incremental estimate can over-count duplicate additions
            # within a batch) — no host round trip of the mask.
            load = jnp.asarray(
                packed.storage_per_server(f_arr).astype(np.float32)
            )
        if track_rm or collect_additions:
            ch = np.asarray(chosen)[:k]
            sv = np.asarray(srv)[:k]
            bb, xx, kk = np.nonzero(ch)
            if collect_additions:
                add_obj.append(o[bb, xx].astype(np.int64))
                add_srv.append(sv[bb, kk].astype(np.int64))
            if track_rm:
                fo = np.asarray(first_obj)[:k]
                for b, x, kk_ in zip(bb, xx, kk):
                    stats.rm.append(
                        (int(fo[b, kk_]), int(o[b, x]), int(sv[b, kk_]))
                    )
    if fused:
        if acc_holder is not None:
            # deferred: keep the stats on device, drained at stream end
            acc_holder.acc = acc
        else:
            # one device->host readback for the whole class (pad rows are
            # inert in every component, see _fused_update_batch)
            a = np.asarray(acc)
            stats.total_cost += float(a[0])
            stats.failed_paths += int(a[1])
            stats.routed_skips += int(a[2])
            if obs.enabled():
                obs.REGISTRY.counter("repro.greedy.stat_readbacks").inc()
    if mesh is not None:
        packed.words = _sharding.one_device(packed.words, mesh)
        load = _sharding.one_device(load, mesh)
    additions = (
        (
            np.concatenate(add_obj) if add_obj else np.zeros(0, np.int64),
            np.concatenate(add_srv) if add_srv else np.zeros(0, np.int64),
        )
        if collect_additions
        else None
    )
    return load, additions


# host-residency bound on candidate-table construction: a budget class
# whose padded C(h, t) table holds more rows than this is assembled on
# device from streamed chunks instead of one host materialization
_TABLE_STREAM_ROWS = 2048


def _tables_to_device(H: int, b: int, stats: "GreedyStats | None" = None):
    """Device candidate tables for budget b, streaming when they are big.

    Small tables (padded row count <= ``_TABLE_STREAM_ROWS``) take the
    cached :func:`combi.stacked_tables` host build — bit-identical to the
    historical path.  Bigger tables are assembled *on device*: start from
    ``jnp.ones`` (the same inert all-True padding the host build uses) and
    scatter bounded row chunks from :func:`combi.iter_comb_rows` into
    place, so host residency peaks at one chunk regardless of C(H, t).
    The two constructions produce identical device arrays by design.
    """
    counts_np = np.array(
        [combi.n_candidates(h, b) for h in range(H + 1)], np.int32
    )
    c_max = int(counts_np.max())
    if c_max <= _TABLE_STREAM_ROWS:
        tables_np, counts_full = combi.stacked_tables(H, b)
        if stats is not None:
            rows = (H + 1) * c_max  # the whole padded table is host-built
            stats.table_peak_rows = max(stats.table_peak_rows, rows)
            stats.table_total_rows += int(counts_np.sum())
        return to_device(tables_np), to_device(counts_full)
    tables = jnp.ones((H + 1, c_max, H + 1), dtype=bool)
    peak = 0
    total = 0
    for h in range(H + 1):
        r0 = 0
        for chunk in combi.iter_comb_rows(h, b, _TABLE_STREAM_ROWS):
            rows = chunk.shape[0]
            tables = tables.at[h, r0 : r0 + rows, : h + 1].set(
                to_device(chunk)
            )
            r0 += rows
            peak = max(peak, rows)
            total += rows
    if stats is not None:
        stats.table_peak_rows = max(stats.table_peak_rows, peak)
        stats.table_total_rows += total
    return tables, to_device(counts_np)


def _budget_class_plan(
    ps: PathSet,
    t_path: np.ndarray,
    shard_j,
    max_candidates: int,
    skip_tables: bool = False,
    stats: "GreedyStats | None" = None,
):
    """Bucket paths by distinct latency budget (ascending, tightest first).

    The candidate enumeration tables C(h, t) and the vectorizable/sequential
    split both depend on t, so each distinct budget gets its own tables and
    its own H_vec.  Yields ``(budget, class_pathset, vec_idx, seq_idx,
    h_all, tables, counts)`` per class; with a uniform budget vector this
    is one class covering every path in workload order — bit-identical to
    the old scalar driver.  Processing tightest budgets first lets looser
    paths reuse the replicas the tight ones forced (sound by Thm 5.3:
    existing replicas only lower candidate costs).

    ``skip_tables`` (policy-aware drivers) yields None tables/counts: the
    routed class filter rebuilds them on the surviving paths anyway, so
    building+uploading them here would be dead work.
    """
    plan = []
    for b in np.unique(t_path):
        b = int(b)
        idx = np.nonzero(t_path == b)[0]
        cls = ps.select(idx)
        _, _, h_all = subpath_structure(
            jnp.asarray(cls.objects), jnp.asarray(cls.lengths), shard_j
        )
        h_all = np.asarray(h_all)
        H_needed = int(h_all.max()) if cls.n_paths else 0
        H_vec = combi.max_h_within_budget(b, max_candidates, H_needed)
        vec_idx = np.nonzero(h_all <= H_vec)[0]
        seq_idx = np.nonzero(h_all > H_vec)[0]
        if skip_tables:
            tables = counts = None
        else:
            tables, counts = _tables_to_device(max(H_vec, b, 1), b, stats)
        plan.append((b, cls, vec_idx, seq_idx, h_all, tables, counts))
    return plan


def _routed_violation_idx(routed_fn, ps: PathSet, t_path: np.ndarray):
    """Indices of paths over budget under the routed policy (one eval)."""
    h_rt = np.asarray(
        routed_fn(
            np.asarray(ps.objects, np.int32), np.asarray(ps.lengths, np.int32)
        ),
        np.int64,
    )
    return np.nonzero(h_rt > t_path)[0]


def _routed_eval_rows(routed_fn, ps, rows: np.ndarray) -> np.ndarray:
    """Routed h for a compacted subset of ``ps``'s rows (128-row buckets).

    Pads the gathered block up to a 128-row quantum (-1 objects / 0
    lengths — empty paths, h = 0) so varying dirty-set sizes hit a
    bounded set of jit traces, exactly the incremental evaluator's
    padding discipline.
    """
    D = len(rows)
    Db = -(-max(D, 1) // 128) * 128
    o = np.full((Db, ps.objects.shape[1]), -1, np.int32)
    ln = np.zeros(Db, np.int32)
    o[:D] = np.asarray(ps.objects, np.int32)[rows]
    ln[:D] = np.asarray(ps.lengths, np.int32)[rows]
    return np.asarray(routed_fn(o, ln), np.int64)[:D]


def _revalidate_routed(routed_fn, ps, t_path, run_classes, stats,
                       index=None) -> None:
    """Bounded re-validation after a policy-aware pass.

    Receding-horizon walks are not monotone under foreign replica
    additions, so a path gated out early can regress by the end of the
    pass: re-run UPDATE over the violating paths for up to
    ``_POLICY_REVALIDATE`` rounds and record whatever residue survives in
    ``stats.routed_violations`` (0 = the scheme is routed-feasible for
    every processed path; callers must not assume feasibility otherwise).

    With ``index`` (a :class:`~repro.engine.incremental.PathIndex` over
    ``ps``) each round after an UPDATE re-walks only the *dirty* rows:
    the UPDATE adds copies solely of objects on the paths it processed,
    and a routed walk reads only its own objects' replica rows, so paths
    outside ``index.dirty_paths(ps.objects[viol])`` provably kept their
    latency — the per-round saving lands in
    ``stats.revalidate_rows_saved``.
    """
    viol = _routed_violation_idx(routed_fn, ps, t_path)
    for _ in range(_POLICY_REVALIDATE):
        if not len(viol):
            break
        run_classes(ps.select(viol), t_path[viol])
        if index is not None:
            cand = index.dirty_paths(np.asarray(ps.objects)[viol])
            stats.revalidate_rows_saved += int(ps.n_paths - len(cand))
            h = _routed_eval_rows(routed_fn, ps, cand)
            viol = cand[h > t_path[cand]]
        else:
            viol = _routed_violation_idx(routed_fn, ps, t_path)
    stats.routed_violations = int(len(viol))


def _routed_gate_fn(packed: PackedScheme, pol, backend: str, block: int = 128,
                    load=None):
    """Routed-latency evaluator over the evolving packed snapshot.

    Returns ``fn(objects, lengths) -> int32 [B]`` computing
    h(p, r, rho; policy) against ``packed``'s *current* words, or None
    when no gating is wanted (``pol`` is None / home_first — the closed
    form the UPDATE already prices).  ``backend`` picks the
    implementation: ``jnp`` (vectorized scan), ``pallas`` (the
    policy-parameterized routed-walk kernel), or ``reference`` (the
    pure-python oracle against a per-call readback — the parity anchor).
    ``load`` is the forecast per-server load profile a ``queue_aware``
    policy prices the gate with (ignored by load-blind policies).
    """
    if pol is None:
        return None
    if backend == "reference":
        from repro.core.reference import (  # lazy: no cycle at import
            routed_path_latencies_reference,
        )

        def fn(objects, lengths):
            return routed_path_latencies_reference(
                np.asarray(objects, np.int32),
                np.asarray(lengths, np.int32),
                packed.unpack(),
                np.asarray(packed.shard),
                policy=pol,
                load=load,
            )

        return fn
    if backend not in ("jnp", "pallas"):
        raise ValueError(
            f"unknown policy_backend {backend!r}; use reference | jnp | pallas"
        )
    from repro.engine import backends as _backends

    if backend == "pallas":

        def fn(objects, lengths):
            return np.asarray(
                _backends.pallas_routed_eval(
                    to_device(np.asarray(objects, np.int32)),
                    to_device(np.asarray(lengths, np.int32)),
                    packed.words,
                    packed.shard,
                    pol,
                    load=load,
                    block=block,
                )
            )

        return fn

    def fn(objects, lengths):
        return np.asarray(
            _backends.routed_counts(
                to_device(np.asarray(objects, np.int32)),
                to_device(np.asarray(lengths, np.int32)),
                packed.words,
                packed.shard,
                pol,
                load=load,
            )
        )

    return fn


def _routed_class_filter(
    cls: PathSet, b: int, h_all: np.ndarray, routed_fn, max_candidates: int,
    stats: "GreedyStats | None" = None,
):
    """Rebuild one budget class's plan on the routed walk.

    Evaluates the class's paths under the routed policy against the
    current snapshot, drops the ones already within budget (the expensive
    enumeration fallbacks included), and re-derives H_vec + the C(h, t)
    tables from the *surviving* paths only.  Returns
    ``(vec_idx, seq_idx, tables, counts, n_skipped)``.
    """
    h_rt = np.asarray(
        routed_fn(
            np.asarray(cls.objects, np.int32), np.asarray(cls.lengths, np.int32)
        ),
        np.int64,
    )
    kept = np.nonzero(h_rt > b)[0]
    # only structurally-infeasible paths the routed walk rescued count as
    # skips (h <= b paths were no-ops under the closed form too)
    n_skipped = int(((h_all > b) & (h_rt <= b)).sum())
    H_needed = int(h_all[kept].max()) if len(kept) else 0
    H_vec = combi.max_h_within_budget(b, max_candidates, H_needed)
    vec_idx = kept[h_all[kept] <= H_vec]
    seq_idx = kept[h_all[kept] > H_vec]
    tables, counts = _tables_to_device(max(H_vec, b, 1), b, stats)
    return vec_idx, seq_idx, tables, counts, n_skipped


def _fused_setup(packed: PackedScheme, pol, load, fused: bool, mesh,
                 batch_size: int):
    """Shared fused-driver preamble: the gate holder-rank vector and the
    batch size rounded to a device-count multiple."""
    if not fused:
        if mesh is not None:
            raise ValueError("mesh= requires fused=True")
        return None, batch_size
    from repro.engine.backends import _load_vector  # lazy: no cycle at import

    rank = _load_vector(
        load if (pol is not None and pol.uses_load) else None, packed.words
    )
    if mesh is not None:
        nd = int(np.prod(list(mesh.shape.values())))
        batch_size = -(-batch_size // nd) * nd
    return rank, batch_size


def _capacity_arrays(n_servers: int, capacity, epsilon):
    check = capacity is not None or epsilon is not None
    cap_arr = np.full((n_servers,), np.inf, np.float32)
    if capacity is not None:
        cap_arr = np.broadcast_to(
            np.asarray(capacity, np.float32), (n_servers,)
        ).copy()
    eps = np.float32(epsilon if epsilon is not None else np.inf)
    return check, jnp.asarray(cap_arr), jnp.asarray(eps)


# routed-feasibility re-validation rounds after a policy-aware pass: the
# receding-horizon walks are not strictly monotone under foreign replica
# additions, so a path gated out early is re-checked against the final
# scheme and re-run through UPDATE if it regressed (rare; each round only
# touches the violating paths)
_POLICY_REVALIDATE = 2

# masked-repair rounds for the k-resilience gate: with rotation-failover
# homes the home_first masked walk is monotone per loss case (one round
# closes each case for good — Thm 5.3 applies case-by-case), so extra
# rounds only serve the receding-horizon policies, mirroring
# _POLICY_REVALIDATE
_RESILIENCE_ROUNDS = 3


def _resilient_eval(packed: PackedScheme, ps: PathSet, cases, homes,
                    pol, policy_backend: str, load) -> np.ndarray:
    """h per (loss case, path) against ``packed``'s current words.

    The gate's masked re-walk: loss case d clears its servers' holder
    bits and walks under the rotation-failover homes ``homes[d]``.
    ``policy_backend`` keeps the three-way parity discipline — the jnp
    path batches all cases into one vmapped dispatch, pallas lowers each
    case to the routed-walk kernels, reference loops the pure-python
    oracle over per-case host masks.
    """
    objects = np.asarray(ps.objects, np.int32)
    lengths = np.asarray(ps.lengths, np.int32)
    if policy_backend == "reference":
        from repro.core.reference import (  # lazy: no cycle at import
            path_latencies_reference,
            routed_path_latencies_reference,
        )

        mask = packed.unpack()
        rows = []
        for c, fs in zip(cases, homes):
            m = mask.copy()
            m[:, np.asarray(c)] = False
            if pol is None:
                rows.append(path_latencies_reference(objects, lengths, m, fs))
            else:
                rows.append(routed_path_latencies_reference(
                    objects, lengths, m, fs, policy=pol, load=load
                ))
        return np.stack(rows).astype(np.int64)
    from repro.engine import backends as _backends  # lazy: no cycle
    from repro.engine.resilience import case_word_mask  # lazy: no cycle

    W = int(packed.words.shape[1])
    case_masks = np.stack([case_word_mask(c, W) for c in cases])
    out = _backends.resilient_counts(
        to_device(objects),
        to_device(lengths),
        packed.words,
        to_device(case_masks),
        to_device(np.stack(homes).astype(np.int32)),
        policy=pol,
        load=load,
        backend=policy_backend,
    )
    return np.asarray(out).astype(np.int64)


def _repair_loss_case(
    packed: PackedScheme,
    sub_ps: PathSet,
    t_sub: np.ndarray,
    fshard: np.ndarray,
    cmask_words: np.ndarray,
    orphans: np.ndarray,
    pol,
    policy_backend: str,
    f_arr: np.ndarray,
    f_j,
    capacity,
    epsilon,
    cap_j,
    eps_j,
    check_capacity: bool,
    batch_size: int,
    max_candidates: int,
    stats: GreedyStats,
    load,
    fused: bool,
    track_rm: bool,
):
    """One masked UPDATE pass: provision ``sub_ps`` as if the loss case
    had already happened.

    Builds a temporary :class:`PackedScheme` view — the live words with
    the lost servers' holder bits cleared, sharded by the case's
    rotation-failover homes — and runs the same batched UPDATE machinery
    (routed gate included) against it.  ``orphans`` are the violating
    paths' objects whose home the case took down and whose failover home
    holds no copy yet: they are **re-homed first** (a copy provisioned at
    the rotation target), because the UPDATE's closed-form cost model
    prices every object as free at its own home — an assumption the
    masked scheme breaks exactly at the orphans (and the assumption a
    real system restores by resharding off a dead server; re-homing is
    also what makes the data itself survive the case).  Every candidate
    server is a failover home, hence alive under the case by
    construction; capacity is checked on the masked load, which equals
    the live load on every surviving server.  Returns the applied
    (object, server) additions — orphan re-homes included — for the
    caller to replay into the live scheme (Thm 5.3: replaying them can
    only lower latencies of the unmasked walk too).
    """
    from repro.engine.backends import mask_case_words  # lazy: no cycle

    masked = PackedScheme(
        words=mask_case_words(packed.words, to_device(cmask_words)),
        shard=to_device(np.asarray(fshard, np.int32)),
        n_servers=packed.n_servers,
    )
    if len(orphans):
        masked.add(orphans, np.asarray(fshard)[orphans])
    routed_fn = _routed_gate_fn(masked, pol, policy_backend, load=load)
    fused_c = fused and policy_backend != "reference"
    use_pallas = fused_c and policy_backend == "pallas"
    rank, bsz = _fused_setup(masked, pol, load, fused_c, None, batch_size)
    srv_load = jnp.asarray(masked.storage_per_server(f_arr).astype(np.float32))
    host_scheme: ReplicationScheme | None = None
    add_obj: list[np.ndarray] = []
    add_srv: list[np.ndarray] = []
    if len(orphans):
        add_obj.append(np.asarray(orphans, np.int64))
        add_srv.append(np.asarray(fshard, np.int64)[orphans])
    for b, cls, vec_idx, seq_idx, h_all, tables, counts in _budget_class_plan(
        sub_ps, t_sub, masked.shard, max_candidates,
        skip_tables=routed_fn is not None, stats=stats,
    ):
        if routed_fn is not None and cls.n_paths:
            vec_idx, seq_idx, tables, counts, n_skip = _routed_class_filter(
                cls, b, h_all, routed_fn, max_candidates, stats=stats
            )
            stats.routed_skips += n_skip
        srv_load, additions = _run_update_batches(
            masked,
            cls.objects[vec_idx],
            cls.lengths[vec_idx],
            masked.shard,
            f_arr,
            f_j,
            tables,
            counts,
            np.full(len(vec_idx), b, np.int32),
            srv_load,
            cap_j,
            eps_j,
            check_capacity,
            bsz,
            stats,
            track_rm,
            collect_additions=True,
            routed_fn=None if fused_c else routed_fn,
            fused=fused_c,
            pol=pol,
            rank=rank,
            use_pallas=use_pallas,
        )
        add_obj.append(additions[0])
        add_srv.append(additions[1])
        if len(seq_idx):
            # exact fallback against the masked host view; additions are
            # replayed into the masked words so later classes see them
            if host_scheme is None:
                host_scheme = ReplicationScheme(
                    masked.unpack(), np.asarray(fshard, np.int32)
                )
            else:
                host_scheme.mask = masked.unpack()
            fb_obj: list[int] = []
            fb_srv: list[int] = []
            for i in seq_idx:
                res = update_exact(
                    host_scheme, cls.path(int(i)), b, f_arr, capacity,
                    epsilon, policy=pol, load=load,
                )
                stats.fallback_paths += 1
                if res.feasible:
                    stats.total_cost += res.cost
                    fb_obj.extend(v for v, _ in res.additions)
                    fb_srv.extend(s for _, s in res.additions)
                    if track_rm:
                        stats.rm.extend(res.rm_entries)
                else:
                    stats.failed_paths += 1
            if fb_obj:
                masked.add(np.asarray(fb_obj), np.asarray(fb_srv))
                add_obj.append(np.asarray(fb_obj, np.int64))
                add_srv.append(np.asarray(fb_srv, np.int64))
                if check_capacity:
                    srv_load = jnp.asarray(
                        masked.storage_per_server(f_arr).astype(np.float32)
                    )
    return (
        np.concatenate(add_obj) if add_obj else np.zeros(0, np.int64),
        np.concatenate(add_srv) if add_srv else np.zeros(0, np.int64),
    )


def _enforce_resilience(
    packed: PackedScheme,
    ps: PathSet,
    t_path: np.ndarray,
    res,
    pol,
    policy_backend: str,
    f_arr: np.ndarray,
    f_j,
    capacity,
    epsilon,
    cap_j,
    eps_j,
    check_capacity: bool,
    batch_size: int,
    max_candidates: int,
    stats: GreedyStats,
    load,
    fused: bool,
    track_rm: bool,
):
    """The k-resilience gate: repair every loss case until none violates.

    Per bounded round: evaluate h under every loss case of ``res`` (one
    batched masked re-walk), then for each violating case run the masked
    UPDATE over its violating paths and scatter-OR the chosen additions
    into the LIVE words — so later cases and rounds price against them.
    The surviving (case, path) violations land in
    ``stats.resilient_violations``; 0 means the returned scheme stays
    latency-feasible under the loss of any single server / fault domain
    combination the constraint names.  Returns the applied (object,
    server) additions.
    """
    from repro.engine.resilience import (  # lazy: no cycle at import
        case_word_mask,
        failover_shard,
    )

    n_servers = packed.n_servers
    shard_host = np.asarray(packed.shard)
    cases = res.loss_cases(n_servers)
    homes = [failover_shard(shard_host, c, n_servers) for c in cases]
    W = int(packed.words.shape[1])
    all_obj: list[np.ndarray] = []
    all_srv: list[np.ndarray] = []
    for rnd in range(_RESILIENCE_ROUNDS + 1):
        h_cases = _resilient_eval(
            packed, ps, cases, homes, pol, policy_backend, load
        )
        viol = h_cases > t_path[None, :]
        total = int(viol.sum())
        if total == 0 or rnd == _RESILIENCE_ROUNDS:
            stats.resilient_violations = total
            break
        stats.resilience_rounds += 1
        mask_host = packed.unpack()
        for d, c in enumerate(cases):
            idx = np.nonzero(viol[d])[0]
            if not len(idx):
                continue
            # objects the case orphans: homed on a lost server, no copy
            # at the rotation failover home yet — re-homed by the repair
            vobj = np.unique(np.asarray(ps.objects)[idx])
            vobj = vobj[vobj >= 0]
            dead = np.zeros(n_servers, bool)
            dead[np.asarray(c)] = True
            orphans = vobj[
                dead[shard_host[vobj]] & ~mask_host[vobj, homes[d][vobj]]
            ]
            obj, srv = _repair_loss_case(
                packed, ps.select(idx), t_path[idx], homes[d],
                case_word_mask(c, W), orphans, pol, policy_backend,
                f_arr, f_j, capacity, epsilon, cap_j, eps_j,
                check_capacity, batch_size, max_candidates, stats, load,
                fused, track_rm,
            )
            if len(obj):
                # replay into the live scheme: monotone adds, all targets
                # alive under the case (failover homes by construction)
                packed.add(obj, srv)
                mask_host[obj, srv] = True  # keep later cases' orphan filter exact
                all_obj.append(obj)
                all_srv.append(srv)
    return (
        np.concatenate(all_obj) if all_obj else np.zeros(0, np.int64),
        np.concatenate(all_srv) if all_srv else np.zeros(0, np.int64),
    )


def replicate_workload(
    pathset: PathSet,
    shard: np.ndarray,
    n_servers: int,
    t,
    f: np.ndarray | None = None,
    capacity: np.ndarray | float | None = None,
    epsilon: float | None = None,
    batch_size: int = 256,
    max_candidates: int = 2048,
    prune: bool = True,
    track_rm: bool = False,
    return_engine: bool = False,
    policy=None,
    policy_backend: str = "jnp",
    policy_prune: bool = True,
    load: np.ndarray | None = None,
    fused: bool = False,
    mesh=None,
    resilience=None,
):
    """Alg 1 over a workload with the vectorized batched UPDATE.

    Args mirror Def 4.4: ``t`` is the latency constraint — an int (every
    query shares one bound), a per-query int vector, or an
    :class:`~repro.core.slo.SLOSpec` (per-tenant budgets); ``f`` the
    storage cost function, ``capacity`` M_s, ``epsilon`` the load imbalance
    bound.  ``track_rm`` additionally accumulates the §5.4 resharding map
    entries (u, v, s).

    Vector budgets bucket paths into budget classes (tightest first); each
    class runs the same batched UPDATE with its own candidate tables, so
    ``replicate_workload(ps, ..., t=k)`` and
    ``replicate_workload(ps, ..., t=SLOSpec.uniform(k, nq))`` produce
    bit-identical schemes.

    ``policy`` (str | ``repro.engine.routing.RoutingPolicy``) prices every
    candidate under that *routed* walk instead of the home-first closed
    form: per budget class the C(h, t) tables are rebuilt on the paths the
    routed walk cannot already serve, and every batch gates additions on
    h(p, r, rho; policy) <= t_q against the same snapshot it costs
    candidates on — a path existing replicas already serve buys nothing
    (``stats.routed_skips`` counts them).  After the main pass the routed
    feasibility of the whole workload is re-validated and any regressed
    paths re-run (bounded rounds).  ``policy="home_first"`` / ``None`` is
    the historical driver, bit-identical.  ``policy_backend`` selects the
    gate's evaluator: ``jnp`` | ``pallas`` (the policy-parameterized
    routed-walk kernel) | ``reference`` (pure-python oracle).

    The gate only prices a path against the replicas of *earlier*
    batches (lock-free snapshot semantics — within one batch every path
    still pays home-first style), so with ``policy_prune=True`` (the
    default for policy runs) the driver finishes with one
    :func:`~repro.core.replication.prune_scheme_replicas` sweep under the
    same policy, dropping the within-batch redundancy the snapshot could
    not see; ``stats.pruned_replicas`` counts the drops and the returned
    scheme/engine reflect them.

    The evolving scheme lives on device as the engine's packed uint32
    bitmask; every batch bit-tests candidates against that snapshot and
    applies the chosen additions with one on-device scatter-OR — the
    unpacked bool mask is read back once per budget class that needs the
    exact fallback, plus once at the end.  With ``return_engine=True`` the
    returned tuple gains a ``LatencyEngine`` that still holds the final
    scheme device-resident, so follow-up feasibility sweeps skip the
    re-upload entirely.

    ``load`` is a forecast per-server load profile: a ``queue_aware``
    policy prices the gate (and the exact fallbacks, the revalidation
    rounds, and the final prune) with it instead of the static zero-load
    default — provision-time load awareness.  Load-blind policies ignore
    it.

    ``fused`` replaces the separate-dispatch pipeline (host-driven gate
    eval + UPDATE + per-batch stat readbacks) with one fused jit step per
    batch — gate + candidate scoring + bit-test + scatter-OR in a single
    dispatch, statistics reduced on device (``policy_backend="pallas"``
    lowers the step to the ``kernels.provision_update`` megakernel) — and
    prices the final prune sweep with the batched independent-group
    plan.  Bit-identical to ``fused=False`` by construction (asserted
    across the full policy x backend matrix in
    tests/test_provision_scale.py).  ``mesh`` (a ``jax.sharding.Mesh``
    from ``repro.engine.sharding.provisioning_mesh``) additionally shards
    every batch across devices on the path axis while the packed words
    stay replicated (requires ``fused=True``).

    ``resilience`` (int k | :class:`~repro.engine.KResilient` | None)
    adds the k-resilience gate: after the ordinary pass (and the policy
    prune — pruning decides on the non-resilient criterion, so it must
    not run after the resilience replicas land) every loss case of the
    constraint is evaluated as a masked re-walk — the lost servers'
    holder bits cleared, homes remapped by rotation failover — batched
    across cases in the same fused UPDATE machinery, and each violating
    (case, path) pair is re-run through UPDATE against the masked
    snapshot.  The additions are replayed into the live scheme (sound by
    Thm 5.3).  ``stats.resilient_violations == 0`` certifies the
    returned scheme stays latency-feasible under the loss of any single
    server / any k fault domains.
    """
    from repro.core.slo import normalize_path_budgets  # local: no cycle at import
    from repro.engine.resilience import resolve_resilience  # local: no cycle
    from repro.engine.routing import resolve_policy  # local: no cycle at import

    t0 = time.perf_counter()
    n = shard.shape[0]
    pol = resolve_policy(policy)
    pol = None if pol.name == "home_first" else pol
    res = resolve_resilience(resilience)
    t_path = normalize_path_budgets(t, pathset)
    if prune:
        # the budget joins the §5.3 dedup key: a tight-budget path must not
        # be merged into a loose-budget duplicate (constraint would vanish)
        ps, keep = pathset.prune_redundant(
            shard, extra_key=t_path, return_index=True
        )
        t_path = t_path[keep]
    else:
        ps = pathset
    scheme = ReplicationScheme.from_sharding(shard, n_servers)
    stats = GreedyStats(rm=[] if track_rm else None)
    stats.paths_processed = ps.n_paths
    if ps.n_paths == 0:
        stats.runtime_s = time.perf_counter() - t0
        if return_engine:
            return scheme, stats, LatencyEngine(scheme)
        return scheme, stats

    f_arr = np.ones((n,), np.float32) if f is None else f.astype(np.float32)
    packed = PackedScheme.from_sharding(scheme.shard, n_servers)
    shard_j = packed.shard
    f_j = to_device(f_arr)

    check_capacity, cap_j, eps_j = _capacity_arrays(n_servers, capacity, epsilon)
    srv_load = jnp.asarray(scheme.storage_per_server(f_arr).astype(np.float32))
    routed_fn = _routed_gate_fn(packed, pol, policy_backend, load=load)
    fused = fused and policy_backend != "reference"
    use_pallas = policy_backend == "pallas"
    rank, batch_size = _fused_setup(
        packed, pol, load, fused, mesh, batch_size
    )

    def run_classes(ps_run: PathSet, t_run: np.ndarray) -> None:
        nonlocal srv_load
        for b, cls, vec_idx, seq_idx, h_all, tables, counts in _budget_class_plan(
            ps_run, t_run, shard_j, max_candidates,
            skip_tables=routed_fn is not None, stats=stats,
        ):
            n_skip = 0
            if routed_fn is not None and cls.n_paths:
                vec_idx, seq_idx, tables, counts, n_skip = _routed_class_filter(
                    cls, b, h_all, routed_fn, max_candidates, stats=stats
                )
                stats.routed_skips += n_skip
            _obs_record_class(stats, b, len(vec_idx), len(seq_idx), counts, n_skip)
            srv_load, _ = _run_update_batches(
                packed,
                cls.objects[vec_idx],
                cls.lengths[vec_idx],
                shard_j,
                f_arr,
                f_j,
                tables,
                counts,
                np.full(len(vec_idx), b, np.int32),
                srv_load,
                cap_j,
                eps_j,
                check_capacity,
                batch_size,
                stats,
                track_rm,
                routed_fn=None if fused else routed_fn,
                fused=fused,
                pol=pol,
                rank=rank,
                use_pallas=use_pallas,
                mesh=mesh,
            )

            # Exact fallback for enumeration-heavy paths (processed after
            # the class's vectorized paths; order is immaterial to
            # correctness by Thm 5.3).  Additions run against a freshly
            # synced host mask and are replayed into the packed words so
            # later classes see them.
            if len(seq_idx):
                scheme.mask = packed.unpack()
                fb_obj: list[int] = []
                fb_srv: list[int] = []
                for i in seq_idx:
                    res = update_exact(
                        scheme, cls.path(int(i)), b, f_arr, capacity,
                        epsilon, policy=pol, load=load,
                    )
                    stats.fallback_paths += 1
                    if res.feasible:
                        stats.total_cost += res.cost
                        fb_obj.extend(v for v, _ in res.additions)
                        fb_srv.extend(s for _, s in res.additions)
                        if track_rm:
                            stats.rm.extend(res.rm_entries)
                    else:
                        stats.failed_paths += 1
                if fb_obj:
                    packed.add(np.asarray(fb_obj), np.asarray(fb_srv))
                    if check_capacity:
                        srv_load = jnp.asarray(
                            packed.storage_per_server(f_arr).astype(np.float32)
                        )

    run_classes(ps, t_path)
    if routed_fn is not None:
        from repro.engine.incremental import PathIndex  # lazy: no cycle

        _revalidate_routed(
            routed_fn, ps, t_path, run_classes, stats,
            index=PathIndex(np.asarray(ps.objects), packed.n_objects),
        )

    # single host readback of the packed words (vs. per-batch bool mask);
    # fallback additions were replayed into the words, so the packed state
    # stays the source of truth and return_engine never loses residency.
    scheme.mask = packed.unpack()

    if pol is not None and policy_prune and stats.paths_processed:
        from repro.core.replication import (  # lazy: no cycle at import
            prune_scheme_replicas,
        )

        stats.pruned_replicas, _ = prune_scheme_replicas(
            scheme, pathset, t, policy=pol, f=f_arr, load=load, fused=fused
        )
        if stats.pruned_replicas:
            # removals are not monotone: the packed words are stale
            packed = PackedScheme.from_mask(scheme.mask, scheme.shard)

    if res is not None and ps.n_paths:
        _enforce_resilience(
            packed, ps, t_path, res, pol, policy_backend, f_arr, f_j,
            capacity, epsilon, cap_j, eps_j, check_capacity, batch_size,
            max_candidates, stats, load, fused, track_rm,
        )
        scheme.mask = packed.unpack()

    stats.replicas = scheme.replica_count()
    stats.runtime_s = time.perf_counter() - t0
    if return_engine:
        return scheme, stats, LatencyEngine(scheme, packed=packed)
    return scheme, stats


def replicate_delta(
    pathset: PathSet,
    engine: LatencyEngine,
    t,
    f: np.ndarray | None = None,
    capacity: np.ndarray | float | None = None,
    epsilon: float | None = None,
    batch_size: int = 256,
    max_candidates: int = 2048,
    prune: bool = True,
    track_rm: bool = False,
    policy=None,
    policy_backend: str = "jnp",
    load: np.ndarray | None = None,
    fused: bool = False,
    mesh=None,
    collect_additions: bool = True,
    stats_acc: DeviceStatsAcc | None = None,
    sync_host: bool = True,
    resilience=None,
):
    """Warm-start incremental UPDATE over *delta* paths (online serving).

    Runs the same batched Alg 2 UPDATE loop as :func:`replicate_workload`,
    but against the scheme an existing :class:`LatencyEngine` already holds
    device-resident — no from-scratch rebuild, no re-upload.  The additions
    are scatter-ORed into the engine's ``PackedScheme`` on device and
    mirrored into the engine's host scheme (when it has one), so a live
    ``Cluster`` sharing that scheme object sees the delta immediately.

    ``t`` is an int, a per-query vector, or an
    :class:`~repro.core.slo.SLOSpec` aligned with ``pathset`` — vector
    budgets run one UPDATE pass per budget class (tightest first), exactly
    like the from-scratch driver.

    ``policy`` prices the delta under the routed walk, exactly as in
    :func:`replicate_workload`: delta paths the resident scheme already
    serves under the policy buy nothing — a controller that scores
    violations under ``nearest_copy`` repairs with the same policy it
    triggered on, instead of over-paying home-first bytes.

    By Thm 5.3 (latency-robustness) the existing replicas can only lower
    candidate costs, never invalidate previously established bounds, so
    warm-starting over a path delta is exactly as sound as processing those
    paths later in a longer from-scratch run — with batch boundaries
    aligned, the two produce identical schemes (see tests/test_serve.py).

    ``load`` / ``fused`` / ``mesh`` mirror :func:`replicate_workload`:
    forecast load pricing for ``queue_aware`` gates, the fused
    single-dispatch UPDATE step, and multi-device path sharding.

    Returns ``(stats, (objects, servers))`` — the greedy stats for the
    delta and the applied replica additions as two int64 arrays (the
    scheme delta a controller ships to the cluster / replays on restart).
    With ``collect_additions=False`` (streamed ingestion: the caller only
    wants the evolving scheme, not the delta) the per-batch chosen-mask
    readbacks are skipped entirely and the returned arrays are empty; the
    engine's host mask, when present, is refreshed from the packed words
    once per class instead of per-pair.

    ``stats_acc`` (fused runs) keeps the device stat accumulator live
    across calls instead of reading it back before returning — the
    returned stats' cost/failed/skipped components stay 0 until the
    caller :meth:`DeviceStatsAcc.drain`\\ s the holder.  ``sync_host=False``
    additionally skips the end-of-call host-mask refresh (the other
    per-call sync point).  Together they make a fused, non-policy call
    fully asynchronous — what :func:`replicate_stream`'s double-buffered
    pipeline needs to overlap chunk ingestion with device compute.

    ``resilience`` mirrors :func:`replicate_workload`: after the delta
    pass the loss cases are re-walked over the delta paths and repaired;
    the resilience additions join the returned delta (a controller
    repairing a failure passes the dead set as a one-domain
    ``KResilient`` to provision survivable copies in the same call).
    """
    from repro.core.slo import normalize_path_budgets  # local: no cycle at import
    from repro.engine.resilience import resolve_resilience  # local: no cycle
    from repro.engine.routing import resolve_policy  # local: no cycle at import

    t0 = time.perf_counter()
    if engine.packed is None:
        engine.packed = PackedScheme.from_mask(
            engine.scheme.mask, engine.scheme.shard
        )
    packed = engine.packed
    shard = engine.host_shard()
    n = packed.n_objects
    n_servers = packed.n_servers
    pol = resolve_policy(policy)
    pol = None if pol.name == "home_first" else pol
    res = resolve_resilience(resilience)
    t_path = normalize_path_budgets(t, pathset)
    if prune:
        ps, keep = pathset.prune_redundant(
            shard, extra_key=t_path, return_index=True
        )
        t_path = t_path[keep]
    else:
        ps = pathset
    stats = GreedyStats(rm=[] if track_rm else None)
    stats.paths_processed = ps.n_paths
    empty = (np.zeros(0, np.int64), np.zeros(0, np.int64))
    if ps.n_paths == 0:
        stats.runtime_s = time.perf_counter() - t0
        return stats, empty

    f_arr = np.ones((n,), np.float32) if f is None else f.astype(np.float32)
    f_j = to_device(f_arr)
    shard_j = packed.shard

    check_capacity, cap_j, eps_j = _capacity_arrays(n_servers, capacity, epsilon)
    srv_load = jnp.asarray(packed.storage_per_server(f_arr).astype(np.float32))
    routed_fn = _routed_gate_fn(packed, pol, policy_backend, load=load)
    fused = fused and policy_backend != "reference"
    use_pallas = policy_backend == "pallas"
    rank, batch_size = _fused_setup(
        packed, pol, load, fused, mesh, batch_size
    )

    add_obj = np.zeros(0, np.int64)
    add_srv = np.zeros(0, np.int64)

    def run_classes(ps_run: PathSet, t_run: np.ndarray) -> None:
        nonlocal srv_load, add_obj, add_srv
        for b, cls, vec_idx, seq_idx, h_all, tables, counts in _budget_class_plan(
            ps_run, t_run, shard_j, max_candidates,
            skip_tables=routed_fn is not None, stats=stats,
        ):
            n_skip = 0
            if routed_fn is not None and cls.n_paths:
                vec_idx, seq_idx, tables, counts, n_skip = _routed_class_filter(
                    cls, b, h_all, routed_fn, max_candidates, stats=stats
                )
                stats.routed_skips += n_skip
            _obs_record_class(stats, b, len(vec_idx), len(seq_idx), counts, n_skip)
            srv_load, additions = _run_update_batches(
                packed,
                cls.objects[vec_idx],
                cls.lengths[vec_idx],
                shard_j,
                f_arr,
                f_j,
                tables,
                counts,
                np.full(len(vec_idx), b, np.int32),
                srv_load,
                cap_j,
                eps_j,
                check_capacity,
                batch_size,
                stats,
                track_rm,
                collect_additions=collect_additions,
                routed_fn=None if fused else routed_fn,
                fused=fused,
                pol=pol,
                rank=rank,
                use_pallas=use_pallas,
                mesh=mesh,
                acc_holder=stats_acc if fused else None,
            )

            # Mirror the vectorized additions into the host scheme FIRST:
            # the exact fallback below prices candidates against the host
            # mask, which must reflect what this class already
            # scatter-ORed into the words (and later classes' fallbacks
            # price against this class).
            if collect_additions:
                cls_obj, cls_srv = additions
                if engine.scheme is not None and len(cls_obj):
                    engine.scheme.mask[cls_obj, cls_srv] = True
                add_obj = np.concatenate([add_obj, cls_obj])
                add_srv = np.concatenate([add_srv, cls_srv])
            elif engine.scheme is not None and len(seq_idx):
                # no per-pair readback requested: the exact fallback below
                # prices against the host mask, so refresh it from the
                # packed truth (one readback) right before it is consumed
                engine.scheme.mask = packed.unpack()
                if obs.enabled():
                    obs.REGISTRY.counter("repro.greedy.mask_syncs").inc()

            # Exact fallback for enumeration-heavy delta paths: run against
            # a host scheme and replay the additions into the
            # device-resident words.
            if len(seq_idx):
                host = (
                    engine.scheme
                    if engine.scheme is not None
                    else engine.to_scheme()
                )
                fb_obj: list[int] = []
                fb_srv: list[int] = []
                for i in seq_idx:
                    res = update_exact(
                        host, cls.path(int(i)), b, f_arr, capacity,
                        epsilon, policy=pol, load=load,
                    )
                    stats.fallback_paths += 1
                    if res.feasible:
                        stats.total_cost += res.cost
                        fb_obj.extend(v for v, _ in res.additions)
                        fb_srv.extend(s for _, s in res.additions)
                        if track_rm:
                            stats.rm.extend(res.rm_entries)
                    else:
                        stats.failed_paths += 1
                if fb_obj:
                    packed.add(np.asarray(fb_obj), np.asarray(fb_srv))
                    if collect_additions:
                        add_obj = np.concatenate(
                            [add_obj, np.asarray(fb_obj, np.int64)]
                        )
                        add_srv = np.concatenate(
                            [add_srv, np.asarray(fb_srv, np.int64)]
                        )
                    if check_capacity:
                        srv_load = jnp.asarray(
                            packed.storage_per_server(f_arr).astype(np.float32)
                        )

    run_classes(ps, t_path)
    if routed_fn is not None:
        from repro.engine.incremental import PathIndex  # lazy: no cycle

        _revalidate_routed(
            routed_fn, ps, t_path, run_classes, stats,
            index=PathIndex(np.asarray(ps.objects), packed.n_objects),
        )

    if res is not None:
        r_obj, r_srv = _enforce_resilience(
            packed, ps, t_path, res, pol, policy_backend, f_arr, f_j,
            capacity, epsilon, cap_j, eps_j, check_capacity, batch_size,
            max_candidates, stats, load, fused, track_rm,
        )
        if len(r_obj):
            if engine.scheme is not None:
                engine.scheme.mask[r_obj, r_srv] = True
            add_obj = np.concatenate([add_obj, r_obj])
            add_srv = np.concatenate([add_srv, r_srv])

    # the UPDATE loop scatter-ORs into packed.words inside jits, bypassing
    # engine.add_replicas — report the touched objects so the engine's
    # incremental latency cache invalidates its exact dirty set.  The
    # additions are copies of objects on the processed paths, so with the
    # per-batch readbacks off the conservative superset is all of them.
    if collect_additions:
        engine.note_changed(add_obj)
    else:
        engine.note_changed(np.asarray(ps.objects))

    if not collect_additions and sync_host and engine.scheme is not None:
        # keep the engine's host mirror consistent at return (the per-pair
        # incremental mirror is what collect_additions=False skipped);
        # sync_host=False defers even this to the caller (streamed
        # ingestion syncs once at stream end)
        engine.scheme.mask = packed.unpack()
        if obs.enabled():
            obs.REGISTRY.counter("repro.greedy.mask_syncs").inc()

    # Dedupe (a batch can choose the same (v, s) for several paths; the
    # scatter-OR is idempotent, but the returned delta is the exact set of
    # new copies — the bytes a controller actually ships).
    if len(add_obj):
        pairs = np.unique(np.stack([add_obj, add_srv], axis=1), axis=0)
        add_obj, add_srv = pairs[:, 0], pairs[:, 1]

    stats.replicas = int(len(add_obj))
    stats.runtime_s = time.perf_counter() - t0
    return stats, (add_obj, add_srv)


def replicate_stream(
    stream,
    shard: np.ndarray,
    n_servers: int,
    t=None,
    f: np.ndarray | None = None,
    capacity: np.ndarray | float | None = None,
    epsilon: float | None = None,
    batch_size: int = 256,
    max_candidates: int = 2048,
    prune: bool = True,
    policy=None,
    policy_backend: str = "jnp",
    load: np.ndarray | None = None,
    fused: bool = True,
    mesh=None,
    return_engine: bool = False,
):
    """Alg 1 over a *streamed* workload — the full path set is never
    host-resident.

    ``stream`` is a :class:`~repro.engine.streaming.PathStream` (or any
    iterable of ``PathSet`` chunks / ``(PathSet, budgets)`` tuples, which
    is wrapped in one): the producer builds each chunk on demand and
    drops it after the yield, so host residency peaks at one chunk
    (``stats.peak_resident_paths`` — the contract
    ``benchmarks/provisioning_scale.py`` asserts).  Each chunk runs the
    warm-started incremental UPDATE (:func:`replicate_delta`) against the
    single device-resident packed scheme; by Thm 5.3 replica additions
    are monotone, so chunked provisioning is exactly as sound as one long
    run with different batch boundaries (paths duplicated across chunks
    re-enter UPDATE, find themselves already served, and buy nothing).

    ``t`` is the default budget for chunks yielded without one; chunks
    yielded as ``(PathSet, budgets)`` override it per chunk.  ``fused``
    defaults on (this is the provisioning-scale entry point) and, with
    ``collect_additions`` off internally, no per-batch readback ever
    crosses the bus.

    Ingestion is **double-buffered** (the engine's ``stream_chunks``
    pipeline shape, applied to provisioning): each chunk's UPDATE passes
    are dispatched with the stat readback *deferred* to a device
    accumulator (:class:`DeviceStatsAcc`) and the host-mask sync skipped,
    so while chunk ``i``'s batches compute on device, the producer
    generator is already materializing chunk ``i + 1`` on the host.  The
    overlapped producer seconds are reported in
    ``stats.ingest_overlap_s`` (and, when the telemetry plane is on, the
    ``repro.stream.ingest_overlap_s`` gauge).  Policy-aware runs
    (``policy=``) still sync per chunk inside the routed gate; the
    pipeline degrades gracefully rather than breaking.

    Returns ``(scheme, stats)``; ``return_engine=True`` appends the
    device-resident :class:`LatencyEngine`.
    """
    from repro.engine.streaming import PathStream, double_buffer  # lazy: no cycle

    t0 = time.perf_counter()
    if not isinstance(stream, PathStream):
        stream = PathStream(stream)
    scheme = ReplicationScheme.from_sharding(shard, n_servers)
    engine = LatencyEngine(scheme)
    stats = GreedyStats()
    fused = fused and policy_backend != "reference"
    acc_holder = DeviceStatsAcc() if fused else None

    def dispatch(item):
        ps, t_chunk = item
        budgets = t if t_chunk is None else t_chunk
        if budgets is None:
            raise ValueError(
                "no latency budget: pass t= or stream (PathSet, t) tuples"
            )
        cstats, _ = replicate_delta(
            ps, engine, budgets, f=f, capacity=capacity, epsilon=epsilon,
            batch_size=batch_size, max_candidates=max_candidates,
            prune=prune, policy=policy, policy_backend=policy_backend,
            load=load, fused=fused, mesh=mesh, collect_additions=False,
            stats_acc=acc_holder, sync_host=False,
        )
        # cost/failed/skipped live in the deferred device accumulator
        # (fused) and drain once after the stream; the host-side components
        # accumulate per chunk as before
        stats.total_cost += cstats.total_cost
        stats.failed_paths += cstats.failed_paths
        stats.paths_processed += cstats.paths_processed
        stats.fallback_paths += cstats.fallback_paths
        stats.routed_skips += cstats.routed_skips
        stats.routed_violations += cstats.routed_violations
        stats.table_peak_rows = max(
            stats.table_peak_rows, cstats.table_peak_rows
        )
        stats.table_total_rows += cstats.table_total_rows
        if cstats.timeline:
            stats.timeline = (stats.timeline or []) + cstats.timeline

    overlap_s = double_buffer(stream, dispatch)
    if acc_holder is not None:
        acc_holder.drain(stats)
    stats.ingest_overlap_s = stream.stats.ingest_overlap_s = overlap_s
    if engine.packed is not None:
        # the one end-of-stream host sync the per-chunk sync_host=False
        # deferred (keeps scheme and the engine's host mirror consistent)
        scheme.mask = engine.packed.unpack()
    stats.replicas = scheme.replica_count()
    stats.peak_resident_paths = stream.stats.peak_resident_paths
    stream.stats.peak_resident_table_rows = stats.table_peak_rows
    stream.stats.total_table_rows = stats.table_total_rows
    stats.runtime_s = time.perf_counter() - t0
    if obs.enabled():
        obs.REGISTRY.gauge("repro.stream.ingest_overlap_s").set(overlap_s)
        obs.REGISTRY.gauge("repro.stream.peak_resident_paths").set(
            stats.peak_resident_paths
        )
        obs.REGISTRY.counter("repro.stream.chunks").inc(stream.stats.chunks)
    if return_engine:
        return scheme, stats, engine
    return scheme, stats
