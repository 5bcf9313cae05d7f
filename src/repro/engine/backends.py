"""The three latency-evaluation backends behind ``LatencyEngine``.

All backends compute the same quantity — h(p, r, rho), the number of
distributed traversals of a path under the access function (paper
Eqns 1-2) — with identical integer semantics:

  ``reference``  pure-python oracle (``repro.core.reference``), host mask.
  ``jnp``        vectorized ``lax.scan`` over the packed device words.
  ``pallas``     ``repro.kernels.path_latency`` TPU kernel (interpret mode
                 off the TPU, ``repro.kernels.interpret_pallas``);
                 inputs are gathered on device from the packed words,
                 so only the int32 path chunk crosses the host boundary.

The legacy unpacked-bool scan (the old ``core.replication``
``_path_latencies_jit``) is retained as ``bool_scan`` for the
``resident=False`` compatibility/benchmark mode that re-uploads the bool
mask per call the way the seed implementation did.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.packed import test_bits
from repro.engine.routing import resolve_policy
from repro.kernels import interpret_pallas
from repro.kernels.path_latency import path_latency_pallas

BACKENDS = ("reference", "jnp", "pallas")


def _valid_home(objects, lengths, shard, fill):
    L = objects.shape[1]
    valid = jnp.arange(L)[None, :] < lengths[:, None]
    safe = jnp.maximum(objects, 0)
    home = jnp.where(valid, shard[safe], fill).astype(jnp.int32)
    return valid, safe, home


@jax.jit
def words_scan(objects, lengths, words, shard):
    """Packed-words ``lax.scan`` walk of the access function."""
    valid, safe, home = _valid_home(objects, lengths, shard, 0)
    rows = words[safe]  # [P, L, W] uint32

    def step(server, xs):
        home_t, rows_t, valid_t = xs
        # rows_t is [P, W]; word select + bit test per lane (Eqn 1):
        widx = server // 32
        bit = (server % 32).astype(jnp.uint32)
        word = jnp.take_along_axis(rows_t, widx[:, None], axis=1)[:, 0]
        local = ((word >> bit) & jnp.uint32(1)).astype(jnp.bool_)
        nxt = jnp.where(local, server, home_t)
        cost = (~local) & valid_t
        nxt = jnp.where(valid_t, nxt, server)
        return nxt, cost

    server0 = home[:, 0]
    xs = (
        jnp.moveaxis(home[:, 1:], 1, 0),
        jnp.moveaxis(rows[:, 1:], 1, 0),
        jnp.moveaxis(valid[:, 1:], 1, 0),
    )
    _, costs = jax.lax.scan(step, server0, xs)
    return jnp.sum(costs.astype(jnp.int32), axis=0)


@jax.jit
def bool_scan(objects, lengths, mask, shard):
    """Legacy unpacked-bool walk (seed ``_path_latencies_jit`` semantics)."""
    valid, safe, home = _valid_home(objects, lengths, shard, 0)
    rloc = mask[safe]  # [P, L, S] bool

    def step(server, xs):
        home_t, rloc_t, valid_t = xs
        local = jnp.take_along_axis(rloc_t, server[:, None], axis=1)[:, 0]
        nxt = jnp.where(local, server, home_t)
        cost = (~local) & valid_t
        nxt = jnp.where(valid_t, nxt, server)
        return nxt, cost

    server0 = home[:, 0]
    xs = (
        jnp.moveaxis(home[:, 1:], 1, 0),
        jnp.moveaxis(rloc[:, 1:], 1, 0),
        jnp.moveaxis(valid[:, 1:], 1, 0),
    )
    _, costs = jax.lax.scan(step, server0, xs)
    return jnp.sum(costs.astype(jnp.int32), axis=0)


@jax.jit
def pallas_prep(objects, lengths, words, shard):
    """Gather the kernel's (home, masks) inputs on device from the words."""
    valid, safe, home = _valid_home(objects, lengths, shard, -1)
    masks = words[safe]  # [P, L, W]
    return home, masks


def pallas_eval(objects, lengths, words, shard, block: int = 128):
    """Pallas-backed chunk evaluation; stays on device end to end."""
    home, masks = pallas_prep(objects, lengths, words, shard)
    return path_latency_pallas(
        home, masks, lengths, block=block, interpret=interpret_pallas()
    )


def reference_eval(objects, lengths, mask, shard) -> np.ndarray:
    """Pure-python oracle over a host mask (``repro.core.reference``)."""
    from repro.core.reference import path_latencies_reference  # lazy: no cycle

    return path_latencies_reference(objects, lengths, mask, shard)


# ---------------------------------------------------------------------------
# Access trace (executor decoration): per-position visited server + locality.
# ---------------------------------------------------------------------------
@jax.jit
def _access_trace_impl(objects, lengths, words, home, start):
    P, L = objects.shape
    valid = jnp.arange(L)[None, :] < lengths[:, None]
    safe = jnp.maximum(objects, 0)
    hrows = home[safe]  # [P, L]
    wrows = words[safe]  # [P, L, W]

    server0 = jnp.where(valid[:, 0], start, 0).astype(jnp.int32)

    def step(server, xs):
        h_t, w_t, v_t = xs
        srv_c = jnp.maximum(server, 0)
        word = jnp.take_along_axis(w_t, (srv_c // 32)[:, None], axis=1)[:, 0]
        bit = (srv_c % 32).astype(jnp.uint32)
        has_local = ((word >> bit) & jnp.uint32(1)).astype(jnp.bool_)
        has_local = has_local & (server >= 0)
        nxt = jnp.where(has_local, server, h_t).astype(jnp.int32)
        nxt = jnp.where(v_t, nxt, server)
        return nxt, (nxt, has_local & v_t)

    xs = (
        jnp.moveaxis(hrows[:, 1:], 1, 0),
        jnp.moveaxis(wrows[:, 1:], 1, 0),
        jnp.moveaxis(valid[:, 1:], 1, 0),
    )
    _, (srv_rest, loc_rest) = jax.lax.scan(step, server0, xs)
    servers = jnp.concatenate(
        [server0[:, None], jnp.moveaxis(srv_rest, 0, 1)], axis=1
    )
    local = jnp.concatenate(
        [valid[:, :1], jnp.moveaxis(loc_rest, 0, 1)], axis=1
    )
    return servers, local


@jax.jit
def _root_home(objects, home):
    return home[jnp.maximum(objects[:, 0], 0)].astype(jnp.int32)


# ---------------------------------------------------------------------------
# Policy-parameterized walk: the per-hop target is a vectorized function of
# (current server, object words, home, load) instead of the constant
# ``home[obj]``.  See ``repro.engine.routing`` for the policy semantics.
# ---------------------------------------------------------------------------
def _unpack_rows(w):
    """[P, W] uint32 -> [P, W*32] bool holder bits (little-endian words)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (w[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
    return bits.reshape(w.shape[0], -1).astype(jnp.bool_)


def _pick_targets(cand, home, load):
    """Best-scoring holder per lane; home wins ties, then lowest id.

    ``cand`` bool [P, Sp] candidate holders, ``home`` int32 [P] (may be
    -1), ``load`` float32 [Sp] (one shared score per server — the
    queue-depth rank) or float32 [P, Sp] (a per-lane score plane — the
    DP cost-to-go of ``nearest_copy_dp``).  Returns int32 [P]; -1 when a
    lane has no candidate.  The scalar twins are
    ``routing.pick_holder_host`` / ``routing.pick_holder_scored``.
    """
    any_c = cand.any(axis=1)
    lv = jnp.where(cand, jnp.broadcast_to(load, cand.shape), jnp.inf)
    m = jnp.min(lv, axis=1)
    best = cand & (lv <= m[:, None])
    hc = jnp.maximum(home, 0)
    home_ok = (home >= 0) & jnp.take_along_axis(best, hc[:, None], axis=1)[:, 0]
    first = jnp.argmax(best, axis=1).astype(jnp.int32)
    tgt = jnp.where(home_ok, home.astype(jnp.int32), first)
    return jnp.where(any_c, tgt, jnp.int32(-1))


@functools.partial(jax.jit, static_argnames=("home_first", "lookahead"))
def _routed_trace_impl(
    objects, lengths, words, home, start, load, home_first, lookahead
):
    """Generalized access walk: hop targets picked by a routing policy.

    With ``home_first=True`` the hop target is ``home[obj]`` — the same
    ops as ``_access_trace_impl`` (bit-identical, asserted in tests).
    Otherwise the target is the holder pick of ``_pick_targets`` over the
    object's packed words (``load`` = zeros gives ``nearest_copy``, live
    queue depths give ``queue_aware``), optionally preferring holders of
    the path's *next* object (``lookahead``).
    """
    P, L = objects.shape
    valid = jnp.arange(L)[None, :] < lengths[:, None]
    safe = jnp.maximum(objects, 0)
    hrows = home[safe]  # [P, L]
    wrows = words[safe]  # [P, L, W]
    # holder words of the NEXT object per step (zeros when x+1 is padding);
    # [P, L-1, W] to match the scan inputs — for L == 1 the scan runs zero
    # steps and the lookahead rows are empty too
    if L > 1:
        wnext = jnp.concatenate(
            [wrows[:, 2:], jnp.zeros_like(wrows[:, :1])], axis=1
        )
        vnext = jnp.concatenate(
            [valid[:, 2:], jnp.zeros_like(valid[:, :1])], axis=1
        )
        wnext = jnp.where(vnext[:, :, None], wnext, jnp.uint32(0))
    else:
        wnext = wrows[:, 1:]

    server0 = jnp.where(valid[:, 0], start, 0).astype(jnp.int32)

    def step(server, xs):
        h_t, w_t, wn_t, v_t = xs
        srv_c = jnp.maximum(server, 0)
        word = jnp.take_along_axis(w_t, (srv_c // 32)[:, None], axis=1)[:, 0]
        bit = (srv_c % 32).astype(jnp.uint32)
        has_local = ((word >> bit) & jnp.uint32(1)).astype(jnp.bool_)
        has_local = has_local & (server >= 0)
        if home_first:
            tgt = h_t
        else:
            cand = _unpack_rows(w_t)
            tgt = _pick_targets(cand, h_t, load)
            if lookahead:
                la = cand & _unpack_rows(wn_t)
                pref = _pick_targets(la, h_t, load)
                tgt = jnp.where(la.any(axis=1), pref, tgt)
        nxt = jnp.where(has_local, server, tgt).astype(jnp.int32)
        nxt = jnp.where(v_t, nxt, server)
        return nxt, (nxt, has_local & v_t)

    xs = (
        jnp.moveaxis(hrows[:, 1:], 1, 0),
        jnp.moveaxis(wrows[:, 1:], 1, 0),
        jnp.moveaxis(wnext, 1, 0),
        jnp.moveaxis(valid[:, 1:], 1, 0),
    )
    _, (srv_rest, loc_rest) = jax.lax.scan(step, server0, xs)
    servers = jnp.concatenate(
        [server0[:, None], jnp.moveaxis(srv_rest, 0, 1)], axis=1
    )
    local = jnp.concatenate(
        [valid[:, :1], jnp.moveaxis(loc_rest, 0, 1)], axis=1
    )
    return servers, local


# ---------------------------------------------------------------------------
# Depth-k suffix DP (``nearest_copy_dp``): score every server by the optimal
# paid-hop count over the next k accesses, then walk with the scored pick.
# ---------------------------------------------------------------------------
def _unpack_positions(wrows):
    """[P, L, W] uint32 -> [P, L, W*32] bool holder bits per position."""
    P, L, W = wrows.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (wrows[:, :, :, None] >> shifts[None, None, None, :]) & jnp.uint32(1)
    return bits.reshape(P, L, W * 32).astype(jnp.bool_)


def _dp_score_tables(objects, lengths, words, depth: int):
    """``E[p, pos, s]``: optimal paid hops over the next ``depth`` accesses.

    The batched twin of ``routing.dp_suffix_scores`` (the dead -1 state is
    tracked in a separate ``D`` plane instead of an extra column).  A hop
    may land on any holder of the hopped-to object; an object with no
    holder sends the walk to the dead state, from which nothing is local
    but later hops still revive.  ``depth < 0`` scores the whole suffix
    (one backward scan); ``depth >= 0`` runs ``depth`` window-widening
    sweeps (each a vectorized position shift).  Returns float32
    ``[P, L, W*32]``.
    """
    P, L = objects.shape
    valid = jnp.arange(L)[None, :] < lengths[:, None]
    safe = jnp.maximum(objects, 0)
    hold = _unpack_positions(words[safe]) & valid[:, :, None]  # [P, L, Sp]
    Sp = hold.shape[2]
    if L == 1:
        return jnp.zeros((P, L, Sp), jnp.float32)

    def hop_cost(hold_next, V_next, D_next):
        lv = jnp.where(hold_next, V_next, jnp.inf)
        vmin = jnp.min(lv, axis=-1)
        any_h = hold_next.any(axis=-1)
        return 1.0 + jnp.where(any_h, vmin, D_next)

    if depth < 0:
        # full suffix: one backward scan, carry = (V at pos+1, dead value)
        def step(carry, xs):
            Vn, Dn = carry
            hold_next, v_next = xs
            hop = hop_cost(hold_next, Vn, Dn)
            V = jnp.where(
                v_next[:, None],
                jnp.where(hold_next, Vn, hop[:, None]),
                0.0,
            )
            D = jnp.where(v_next, hop, 0.0)
            return (V, D), V

        xs = (
            jnp.moveaxis(hold[:, 1:], 1, 0),
            jnp.moveaxis(valid[:, 1:], 1, 0),
        )
        init = (jnp.zeros((P, Sp), jnp.float32), jnp.zeros((P,), jnp.float32))
        _, Vs = jax.lax.scan(step, init, xs, reverse=True)
        return jnp.concatenate(
            [jnp.moveaxis(Vs, 0, 1), jnp.zeros((P, 1, Sp), jnp.float32)],
            axis=1,
        )

    # window-widening sweeps: E_m[pos] from E_{m-1}[pos + 1] (position shift)
    E = jnp.zeros((P, L, Sp), jnp.float32)
    D = jnp.zeros((P, L), jnp.float32)
    hold_next = jnp.concatenate(
        [hold[:, 1:], jnp.zeros((P, 1, Sp), jnp.bool_)], axis=1
    )
    v_next = jnp.concatenate(
        [valid[:, 1:], jnp.zeros((P, 1), jnp.bool_)], axis=1
    )
    for _ in range(depth):
        E_next = jnp.concatenate(
            [E[:, 1:], jnp.zeros((P, 1, Sp), jnp.float32)], axis=1
        )
        D_next = jnp.concatenate(
            [D[:, 1:], jnp.zeros((P, 1), jnp.float32)], axis=1
        )
        hop = hop_cost(hold_next, E_next, D_next)  # [P, L]
        E = jnp.where(
            v_next[:, :, None],
            jnp.where(hold_next, E_next, hop[:, :, None]),
            0.0,
        )
        D = jnp.where(v_next, hop, 0.0)
    return E


def _scored_walk(objects, lengths, words, home, start, scores):
    """The access walk with per-(position, server) hop scores.

    Same scan as ``_routed_trace_impl`` but the remote-hop pick ranks
    holders by ``scores[:, i, :]`` (the DP cost-to-go of landing at each
    server for the hop at position ``i``) instead of a shared load
    vector; home wins ties, then the lowest id.
    """
    P, L = objects.shape
    valid = jnp.arange(L)[None, :] < lengths[:, None]
    safe = jnp.maximum(objects, 0)
    hrows = home[safe]
    wrows = words[safe]

    server0 = jnp.where(valid[:, 0], start, 0).astype(jnp.int32)

    def step(server, xs):
        h_t, w_t, sc_t, v_t = xs
        srv_c = jnp.maximum(server, 0)
        word = jnp.take_along_axis(w_t, (srv_c // 32)[:, None], axis=1)[:, 0]
        bit = (srv_c % 32).astype(jnp.uint32)
        has_local = ((word >> bit) & jnp.uint32(1)).astype(jnp.bool_)
        has_local = has_local & (server >= 0)
        cand = _unpack_rows(w_t)
        tgt = _pick_targets(cand, h_t, sc_t)
        nxt = jnp.where(has_local, server, tgt).astype(jnp.int32)
        nxt = jnp.where(v_t, nxt, server)
        return nxt, (nxt, has_local & v_t)

    xs = (
        jnp.moveaxis(hrows[:, 1:], 1, 0),
        jnp.moveaxis(wrows[:, 1:], 1, 0),
        jnp.moveaxis(scores[:, 1:], 1, 0),
        jnp.moveaxis(valid[:, 1:], 1, 0),
    )
    _, (srv_rest, loc_rest) = jax.lax.scan(step, server0, xs)
    servers = jnp.concatenate(
        [server0[:, None], jnp.moveaxis(srv_rest, 0, 1)], axis=1
    )
    local = jnp.concatenate(
        [valid[:, :1], jnp.moveaxis(loc_rest, 0, 1)], axis=1
    )
    return servers, local


@functools.partial(jax.jit, static_argnames=("depth",))
def _dp_trace_impl(objects, lengths, words, home, start, depth):
    scores = _dp_score_tables(objects, lengths, words, depth)
    return _scored_walk(objects, lengths, words, home, start, scores)


@functools.partial(jax.jit, static_argnames=("depth",))
def _dp_scores_jit(objects, lengths, words, depth):
    return _dp_score_tables(objects, lengths, words, depth)


def _dp_depth(pol) -> int:
    return -1 if pol.depth is None else int(pol.depth)


def _load_vector(load, words) -> jnp.ndarray:
    """Pad a per-server load vector to the words' W*32 bit width.

    Bits past ``n_servers`` are never set in the packed words, so the pad
    value is irrelevant for correctness (padded servers are never
    candidates); zeros keep the array cheap.
    """
    width = words.shape[1] * 32
    out = np.zeros(width, np.float32)
    if load is not None:
        lv = np.asarray(load, np.float32)
        out[: lv.shape[0]] = lv
    return jnp.asarray(out)


def access_trace(objects, lengths, words, home, start=None, policy=None,
                 load=None):
    """Walk Eqn 1 recording the visited server and locality per position.

    ``home`` is a per-object routing target (the sharding function, or the
    executor's fail-over map; may be -1 when no alive copy exists).
    ``start`` optionally overrides the per-path start server (int32 [P]) —
    the router's coordinator pick when it differs from ``home[root]``
    (replica_lb / hedged routing); default is ``home[root]``.

    ``policy`` (str | ``repro.engine.routing.RoutingPolicy``; default
    ``home_first``) selects the remote-hop target rule; ``load`` is the
    per-server queue-depth vector a ``queue_aware`` policy ranks holders
    by (ignored otherwise).

    Returns (servers int32 [P, L], local bool [P, L]); position 0 counts as
    local when the path is non-empty, matching the executor's accounting.
    The distributed-traversal count is ``(valid[:, 1:] & ~local[:, 1:]).sum``.
    """
    pol = resolve_policy(policy)
    if start is None:
        start = _root_home(objects, home)
    if pol.name == "home_first":
        return _access_trace_impl(objects, lengths, words, home, start)
    if pol.name == "nearest_copy_dp":
        return _dp_trace_impl(
            objects, lengths, words, home, start, depth=_dp_depth(pol)
        )
    return _routed_trace_impl(
        objects, lengths, words, home, start,
        _load_vector(load if pol.uses_load else None, words),
        home_first=False, lookahead=pol.lookahead,
    )


@functools.partial(jax.jit, static_argnames=("lookahead",))
def _routed_counts_impl(objects, lengths, words, home, start, load, lookahead):
    _, local = _routed_trace_impl(
        objects, lengths, words, home, start, load,
        home_first=False, lookahead=lookahead,
    )
    L = objects.shape[1]
    valid = jnp.arange(L)[None, :] < lengths[:, None]
    return jnp.sum((valid & ~local).astype(jnp.int32), axis=1)


@functools.partial(jax.jit, static_argnames=("depth",))
def _dp_counts_impl(objects, lengths, words, home, start, depth):
    _, local = _dp_trace_impl(objects, lengths, words, home, start, depth)
    L = objects.shape[1]
    valid = jnp.arange(L)[None, :] < lengths[:, None]
    return jnp.sum((valid & ~local).astype(jnp.int32), axis=1)


def routed_counts(objects, lengths, words, shard, policy, load=None):
    """h(p, r, rho) per path under a non-home-first routing policy."""
    pol = resolve_policy(policy)
    if pol.name == "nearest_copy_dp":
        return _dp_counts_impl(
            objects, lengths, words, shard, _root_home(objects, shard),
            depth=_dp_depth(pol),
        )
    return _routed_counts_impl(
        objects, lengths, words, shard, _root_home(objects, shard),
        _load_vector(load if pol.uses_load else None, words),
        lookahead=pol.lookahead,
    )


def gate_counts(objects, lengths, words, shard, pol, rank, backend="jnp",
                block: int = 128):
    """Traceable routed-gate latencies — callable inside an enclosing jit.

    The fused greedy UPDATE (``repro.core.greedy``) and the batched prune
    sweep compute the policy gate h(p, r, rho; policy) in the *same* jit
    step as candidate scoring and the scatter-OR, against the same words
    snapshot.  ``pol`` must be a resolved, non-home-first policy (a jit
    static — frozen dataclasses hash); ``rank`` the already-padded
    ``[W*32]`` float32 holder-rank vector (``_load_vector`` of the live
    queue depths for ``queue_aware``, zeros otherwise — callers own that
    normalization because this function must stay trace-transparent).
    Dispatch mirrors :func:`routed_counts` / :func:`pallas_routed_eval`
    bit-for-bit, so gating fused vs separate cannot diverge.
    """
    start = _root_home(objects, shard)
    if backend == "pallas":
        from repro.kernels.routed_walk import (  # lazy import
            routed_walk_pallas,
            scored_walk_pallas,
        )

        home, masks = pallas_prep(objects, lengths, words, shard)
        if pol.name == "nearest_copy_dp":
            scores = _dp_score_tables(objects, lengths, words, _dp_depth(pol))
            _, local = scored_walk_pallas(
                home, masks, lengths, start, scores,
                block=block, interpret=interpret_pallas(),
            )
        else:
            _, local = routed_walk_pallas(
                home, masks, lengths, start, rank,
                block=block, interpret=interpret_pallas(),
                lookahead=pol.lookahead, home_first=pol.name == "home_first",
            )
        L = objects.shape[1]
        valid = jnp.arange(L)[None, :] < lengths[:, None]
        return jnp.sum((valid & ~local.astype(bool)).astype(jnp.int32), axis=1)
    if pol.name == "nearest_copy_dp":
        return _dp_counts_impl(
            objects, lengths, words, shard, start, depth=_dp_depth(pol)
        )
    return _routed_counts_impl(
        objects, lengths, words, shard, start, rank, lookahead=pol.lookahead
    )


# ---------------------------------------------------------------------------
# k-resilient evaluation: the masked re-walk over every loss case.
# ---------------------------------------------------------------------------
@jax.jit
def mask_case_words(words, case_mask):
    """Clear one loss case's holder bits: ``words & ~case_mask`` per row."""
    return words & ~case_mask[None, :]


@jax.jit
def _resilient_home_vmap(objects, lengths, words, case_masks, case_homes):
    def one(cmask, home):
        return words_scan(objects, lengths, words & ~cmask[None, :], home)

    return jax.vmap(one)(case_masks, case_homes)


@functools.partial(jax.jit, static_argnames=("lookahead",))
def _resilient_routed_vmap(
    objects, lengths, words, case_masks, case_homes, load, lookahead
):
    def one(cmask, home):
        w = words & ~cmask[None, :]
        return _routed_counts_impl(
            objects, lengths, w, home, _root_home(objects, home), load,
            lookahead=lookahead,
        )

    return jax.vmap(one)(case_masks, case_homes)


@functools.partial(jax.jit, static_argnames=("depth",))
def _resilient_dp_vmap(objects, lengths, words, case_masks, case_homes, depth):
    def one(cmask, home):
        w = words & ~cmask[None, :]
        return _dp_counts_impl(
            objects, lengths, w, home, _root_home(objects, home), depth=depth
        )

    return jax.vmap(one)(case_masks, case_homes)


def resilient_counts(
    objects, lengths, words, case_masks, case_homes, policy=None, load=None,
    backend: str = "jnp", block: int = 128,
):
    """h(p, r - case, rho; policy) per (loss case, path): int32 [D, P].

    The k-resilience gate's masked re-walk, batched across loss cases:
    for each case the lost servers' holder bits are cleared from the
    packed words (``case_masks`` uint32 [D, W]) and the walk runs under
    the case's rotation-failover homes (``case_homes`` int32 [D, n]) —
    see ``repro.engine.resilience``.  The ``jnp`` backend vmaps all D
    cases into one dispatch; ``pallas`` lowers each case's walk to the
    existing path-latency / routed-walk kernels over the masked words
    (the masking itself is one trivial AND, so kernel parity is inherited
    rather than re-implemented).  The reference oracle loops live in
    ``LatencyEngine.resilient_path_latencies`` (they need the host mask).
    """
    pol = resolve_policy(policy)
    if backend == "pallas":
        outs = []
        for d in range(case_masks.shape[0]):
            w = mask_case_words(words, case_masks[d])
            if pol.name == "home_first":
                outs.append(pallas_eval(objects, lengths, w, case_homes[d],
                                        block=block))
            else:
                outs.append(pallas_routed_eval(objects, lengths, w,
                                               case_homes[d], pol, load=load,
                                               block=block))
        return jnp.stack(outs)
    if backend != "jnp":
        raise ValueError(f"resilient_counts backend must be jnp | pallas, got {backend!r}")
    if pol.name == "home_first":
        return _resilient_home_vmap(objects, lengths, words, case_masks, case_homes)
    if pol.name == "nearest_copy_dp":
        return _resilient_dp_vmap(
            objects, lengths, words, case_masks, case_homes, depth=_dp_depth(pol)
        )
    return _resilient_routed_vmap(
        objects, lengths, words, case_masks, case_homes,
        _load_vector(load if pol.uses_load else None, words),
        lookahead=pol.lookahead,
    )


def pallas_routed_trace(
    objects, lengths, words, shard, policy, load=None, block: int = 128,
    start=None,
):
    """Policy-routed walk via the Pallas kernel; (servers, local) arrays."""
    from repro.kernels.routed_walk import (  # lazy import
        routed_walk_pallas,
        scored_walk_pallas,
    )

    pol = resolve_policy(policy)
    home, masks = pallas_prep(objects, lengths, words, shard)
    if start is None:
        start = _root_home(objects, shard)
    if pol.name == "nearest_copy_dp":
        # the score tables are a plain jnp precompute (device-resident);
        # the kernel is the score-parameterized walk over them
        scores = _dp_scores_jit(objects, lengths, words, _dp_depth(pol))
        return scored_walk_pallas(
            home, masks, lengths, start, scores,
            block=block, interpret=interpret_pallas(),
        )
    return routed_walk_pallas(
        home, masks, lengths, start,
        _load_vector(load if pol.uses_load else None, words),
        block=block, interpret=interpret_pallas(),
        lookahead=pol.lookahead, home_first=pol.name == "home_first",
    )


def pallas_routed_eval(
    objects, lengths, words, shard, policy, load=None, block: int = 128
):
    """Distributed-traversal counts from the Pallas policy-routed walk."""
    _, local = pallas_routed_trace(
        objects, lengths, words, shard, policy, load, block=block
    )
    L = objects.shape[1]
    valid = jnp.arange(L)[None, :] < lengths[:, None]
    return jnp.sum((valid & ~local).astype(jnp.int32), axis=1)


@jax.jit
def query_slack(path_lats, query_ids, t_q):
    """Per-query slack t_Q - l_Q against a device-resident budget vector.

    ``path_lats`` int32 [P] (h per path), ``query_ids`` int32 [P],
    ``t_q`` int32 [nq].  l_Q is the max over the query's paths (Def 4.3);
    queries with no paths in the batch have l_Q = 0 (slack = budget).
    Negative slack marks a violating query (Def 4.4 constraint 1).
    """
    nq = t_q.shape[0]
    lq = (
        jnp.zeros((nq,), jnp.int32)
        .at[query_ids]
        .max(path_lats.astype(jnp.int32))
    )
    return t_q - lq


@jax.jit
def margin_cost(words, f, objects, servers):
    """Marginal storage cost of candidate (object, server) additions.

    Snapshot semantics against the device-resident words: each pair whose
    bit is not yet set contributes ``f[v]``; duplicate pairs count once per
    occurrence (the greedy UPDATE's lock-free estimate).  Pairs with a
    negative object or server are ignored.  Reduces over the last axis.
    """
    ok = (objects >= 0) & (servers >= 0)
    o = jnp.maximum(objects, 0)
    s = jnp.maximum(servers, 0)
    present = test_bits(words, o, s)
    need = ok & ~present
    return jnp.sum(jnp.where(need, f[o], 0.0), axis=-1)
