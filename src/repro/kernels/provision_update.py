"""Pallas TPU megakernel: one fused greedy-UPDATE round (Alg 2 hot loop).

One ``pallas_call`` evaluates, per 128-path lane block, everything the
separate-dispatch driver used to round-trip through four kernels:

  1. the policy-routed gate walk h(p, r, rho; policy) against the packed
     snapshot (the ``kernels.routed_walk`` step, shared),
  2. the server-local subpath structure under d (Def 5.1),
  3. every C(h, t) candidate's upward-replication interval mask, bit-tested
     against the holder words (which additions are actually *needed*),
  4. the per-candidate marginal cost + running argmin (ties -> lowest
     candidate index, the driver's determinism rule).

The chosen additions leave the kernel as an ``[L, H+1]`` plane per path;
the wrapper applies them with the engine's ``scatter_or_pairs`` in the
same jit (the scatter's per-bit dynamic updates are XLA's strength and a
lane-parallel kernel's weakness — a per-lane scatter would serialize into
scalar stores on TPU).  Cost / infeasibility / gate-skip statistics reduce
on device; the driver reads one tiny accumulator per budget class instead
of three arrays per batch.

Layout (TPU-native, as in ``routed_walk``): paths on the 128-wide lane
axis, per-path vectors as ``(1, bP)`` rows, one ``(1, bP)`` row per path
position and one ``[H+1, bP]`` plane (subpath k on sublanes) per position
for the candidate logic; L, H and W are small and static, so the position,
subpath and word loops unroll.  Candidate ``c`` of the selection tables is
read from the ref, ``tab_ref[c]``, inside the candidate loop.

Bit-identity contract: every intermediate mirrors
``repro.core.greedy._update_batch_core`` op-for-op (same clipping, same
scatter-max subpath servers, same strict-argmin tie rule), and the gate
walk reuses ``routed_walk``'s step — the three-backend parity matrix
of ``tests/test_provision_scale.py`` pins fused == separate == reference
on every routing policy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.path_latency import DEFAULT_BLOCK, to_rows
from repro.kernels.routed_walk import _hop

_INF = 1e30  # plain float: a jnp scalar here would be a captured kernel constant


def _make_kernel(L: int, W: int, Hp1: int, gate_mode: str, lookahead: bool):
    """``gate_mode``: "none" | "routed" | "scored"."""
    Sp = W * 32

    def kernel(home_ref, mask_ref, len_ref, t_ref, f_ref, start_ref,
               rank_ref, tab_ref, cnt_ref,
               chosen_ref, srv_ref, cost_ref, nosol_ref, skip_ref):
        lens = len_ref[...]                   # int32 [1, bP]
        t = t_ref[...]                        # int32 [1, bP]
        bP = lens.shape[1]
        iota_k = jax.lax.broadcasted_iota(jnp.int32, (Hp1, bP), 0)

        # ---- subpath structure under d (Def 5.1): a static running sum
        # of the subpath boundaries over the positions ----
        home = [home_ref[x:x + 1, :] for x in range(L)]  # -1 at pad positions
        valid = [x < lens for x in range(L)]
        seg, run = [], jnp.zeros_like(lens)
        for x in range(L):
            if x > 0:
                run = run + (valid[x] & (home[x] != home[x - 1])).astype(
                    jnp.int32)
            seg.append(jnp.where(valid[x], run, -1))
        h = jnp.zeros_like(lens)
        for x in range(L):
            h = jnp.maximum(h, jnp.where(valid[x], seg[x], 0))
        h_cl = jnp.clip(h, 0, Hp1 - 1)
        seg_cl = [jnp.clip(s, 0, Hp1 - 1) for s in seg]

        # server of each subpath (scatter-max twin: positions of a subpath
        # share one home; absent subpaths -> -1)
        srv = jnp.zeros((Hp1, bP), jnp.int32)
        for x in range(L):
            srv = jnp.maximum(
                srv, jnp.where(valid[x] & (seg[x] == iota_k), home[x] + 1, 0)
            )
        srv = srv - 1                         # int32 [Hp1, bP]

        # ---- policy-routed gate walk (the routed_walk step) ----
        over = h > t
        if gate_mode == "none":
            gate_ok = over
            skipped = jnp.zeros_like(over)
        else:
            iota_s = jax.lax.broadcasted_iota(jnp.int32, (Sp, bP), 0)
            server0 = jnp.where(lens > 0, start_ref[...], 0).astype(jnp.int32)
            rank = rank_ref[...] if gate_mode == "routed" else None

            def gate_body(i, carry):
                server, cnt = carry
                if gate_mode == "scored":
                    score = rank_ref[pl.ds(pl.multiple_of(i * Sp, Sp), Sp), :]
                else:
                    score = rank
                nxt, local, v = _hop(i, server, lens, home_ref, mask_ref,
                                     score, iota_s, W=W, lookahead=lookahead)
                return nxt, cnt + ((~local) & v).astype(jnp.int32)

            _, h_routed = jax.lax.fori_loop(
                1, L, gate_body, (server0, jnp.zeros_like(lens))
            )
            gate_ok = over & (h_routed > t)
            skipped = over & (h_routed <= t)

        # ---- needed(x, k): no copy of objects[x] at srv[k] yet ----
        srv_c = jnp.maximum(srv, 0)
        w_idx = srv_c >> 5
        b_idx = (srv_c & 31).astype(jnp.uint32)
        needed = []
        for x in range(L):
            word = jnp.zeros((Hp1, bP), jnp.uint32)
            for w in range(W):
                row = x * W + w
                word = jnp.where(w_idx == w, mask_ref[row:row + 1, :], word)
            present = ((word >> b_idx) & jnp.uint32(1)) != 0
            needed.append((~present) & (srv >= 0) & valid[x])

        # ---- candidate loop: running strict argmin (ties -> lowest c) ----
        n_cand = jnp.sum(jnp.where(iota_k == h_cl, cnt_ref[...], 0), axis=0,
                         keepdims=True)       # int32 [1, bP]
        onehot_h = [h_cl == hh for hh in range(Hp1)]
        fpos = [f_ref[x:x + 1, :] for x in range(L)]  # f32, 0 at pad positions
        chosen_ref[...] = jnp.zeros(chosen_ref.shape, jnp.int32)

        def cand_body(c, best_cost):
            tab_c = tab_ref[c]                # int32 [Hp1 (k), Hp1 (h)]
            sel = jnp.zeros((Hp1, bP), jnp.bool_)
            for hh in range(Hp1):
                sel = sel | (onehot_h[hh] & (tab_c[:, hh:hh + 1] != 0))
            adds = []
            cost_pl = jnp.zeros((Hp1, bP), jnp.float32)
            for x in range(L):
                # j(seg_x): the largest selected subpath <= seg_x (or -1)
                j_x = jnp.max(
                    jnp.where(sel & (iota_k <= seg_cl[x]), iota_k, -1),
                    axis=0, keepdims=True,
                )
                window = ((iota_k >= j_x) & (iota_k < seg_cl[x])
                          & valid[x] & gate_ok)
                add = window & needed[x]      # [Hp1, bP]
                adds.append(add)
                cost_pl = cost_pl + add.astype(jnp.float32) * fpos[x]
            cost_c = jnp.sum(cost_pl, axis=0, keepdims=True)
            cost_c = jnp.where(c < n_cand, cost_c, _INF)
            better = cost_c < best_cost
            for x in range(L):
                chosen_ref[x] = jnp.where(
                    better, adds[x].astype(jnp.int32), chosen_ref[x])
            return jnp.where(better, cost_c, best_cost)

        best_cost = jax.lax.fori_loop(
            0, tab_ref.shape[0], cand_body,
            jnp.full((1, bP), _INF, jnp.float32),
        )
        no_sol = best_cost >= _INF
        for x in range(L):
            chosen_ref[x] = jnp.where(no_sol, 0, chosen_ref[x])

        srv_ref[...] = srv
        cost_ref[...] = best_cost
        nosol_ref[...] = no_sol.astype(jnp.int32)
        skip_ref[...] = skipped.astype(jnp.int32)

    return kernel


def _kernel_rounds(home, wrows, lengths, t, fpos, start, rank, tables,
                   counts, *, gate_mode, lookahead, block, interpret):
    """The kernel over a batch's rows (natural ``[B, ...]`` layout in and
    out): (chosen bool [B, L, Hp1], srv [B, Hp1], cost [B],
    no_solution [B], skipped [B])."""
    B, L = home.shape
    W = wrows.shape[2]
    Hp1, C, _ = tables.shape
    Sp = W * 32
    if gate_mode == "scored":
        rank = to_rows(rank, block)                       # [L*Sp, Bp]
        rank_spec = pl.BlockSpec((L * Sp, block), lambda p: (0, p))
    else:
        rank = rank.reshape(Sp, 1)
        rank_spec = pl.BlockSpec((Sp, 1), lambda p: (0, 0))
    home_t = to_rows(home, block, fill=-1)                # [L, Bp]
    Bp = home_t.shape[1]
    # candidate c's selection as a [k, h] plane: tab[c, k, h] = tables[h, c, k]
    tab = jnp.transpose(tables, (1, 2, 0)).astype(jnp.int32)

    row = pl.BlockSpec((1, block), lambda p: (0, p))
    chosen, srv, cost, nosol, skip = pl.pallas_call(
        _make_kernel(L, W, Hp1, gate_mode, lookahead),
        grid=(Bp // block,),
        in_specs=[
            pl.BlockSpec((L, block), lambda p: (0, p)),
            pl.BlockSpec((L * W, block), lambda p: (0, p)),
            row,
            row,
            pl.BlockSpec((L, block), lambda p: (0, p)),
            row,
            rank_spec,
            pl.BlockSpec((C, Hp1, Hp1), lambda p: (0, 0, 0)),
            pl.BlockSpec((Hp1, 1), lambda p: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((L, Hp1, block), lambda p: (0, 0, p)),
            pl.BlockSpec((Hp1, block), lambda p: (0, p)),
            row,
            row,
            row,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L, Hp1, Bp), jnp.int32),
            jax.ShapeDtypeStruct((Hp1, Bp), jnp.int32),
            jax.ShapeDtypeStruct((1, Bp), jnp.float32),
            jax.ShapeDtypeStruct((1, Bp), jnp.int32),
            jax.ShapeDtypeStruct((1, Bp), jnp.int32),
        ],
        interpret=interpret,
    )(home_t, to_rows(wrows, block), to_rows(lengths, block),
      to_rows(t, block), to_rows(fpos, block), to_rows(start, block),
      rank, tab, counts.reshape(Hp1, 1))
    return (
        jnp.transpose(chosen, (2, 0, 1))[:B].astype(bool),
        srv.T[:B],
        cost[0, :B],
        nosol[0, :B].astype(bool),
        skip[0, :B].astype(bool),
    )


def fused_update_pallas(
    words: jnp.ndarray,    # uint32 [(n+1), W] — packed scheme snapshot
    objects: jnp.ndarray,  # int32 [B, L] (-1 padded)
    lengths: jnp.ndarray,  # int32 [B]
    shard: jnp.ndarray,    # int32 [n]
    f: jnp.ndarray,        # float32 [n]
    tables: jnp.ndarray,   # bool [Hp1, C, Hp1] candidate retained-sets
    counts: jnp.ndarray,   # int32 [Hp1]
    t: jnp.ndarray,        # int32 [B] per-path budgets
    rank: jnp.ndarray,     # float32 [W*32] holder-rank (queue_aware load)
    pol=None,              # resolved RoutingPolicy or None (jit static)
    *,
    interpret: bool,
    block: int = DEFAULT_BLOCK,
    mesh=None,
):
    """One fused UPDATE round; traceable (callers jit + donate ``words``).

    Returns ``(words, applied_cost [B], no_solution [B], chosen
    [B, L, Hp1], srv [B, Hp1], skipped [B])`` — the
    ``_update_batch_core`` contract minus capacity/load bookkeeping
    (the driver falls back to the jnp core when capacity checking is on).
    ``interpret`` comes from ``repro.kernels.interpret_pallas``.  With a
    path-axis ``mesh`` (``repro.engine.sharding``) the kernel runs once
    per device on its rows of the batch.
    """
    L = objects.shape[1]
    valid = jnp.arange(L)[None, :] < lengths[:, None]
    safe = jnp.maximum(objects, 0)
    home = jnp.where(valid, shard[safe], -1).astype(jnp.int32)
    fpos = f[safe] * valid.astype(jnp.float32)
    start = shard[jnp.maximum(objects[:, 0], 0)].astype(jnp.int32)

    if pol is None:
        gate_mode, lookahead = "none", False
    elif pol.name == "nearest_copy_dp":
        from repro.engine.backends import _dp_depth, _dp_score_tables

        gate_mode, lookahead = "scored", False
        rank = _dp_score_tables(objects, lengths, words, _dp_depth(pol))
    else:
        gate_mode, lookahead = "routed", bool(pol.lookahead)

    rounds = functools.partial(
        _kernel_rounds, gate_mode=gate_mode, lookahead=lookahead,
        block=block, interpret=interpret,
    )
    if mesh is not None:
        from repro.engine.sharding import map_paths

        rounds = map_paths(
            rounds, mesh, (True,) * 6 + (gate_mode == "scored", False, False)
        )
    chosen, srv, cost, no_solution, skipped = rounds(
        home, words[safe], lengths, t, fpos, start, rank, tables, counts
    )

    # scatter-OR in the same jit: XLA's bit-sliced dynamic-update rounds,
    # not a per-lane kernel scatter (which would serialize on TPU)
    from repro.engine.packed import scatter_or_pairs

    obj_w = jnp.where(chosen, jnp.maximum(objects, 0)[:, :, None], -1)
    srv_w = jnp.broadcast_to(jnp.maximum(srv, 0)[:, None, :], chosen.shape)
    words = scatter_or_pairs(words, obj_w, srv_w)

    applied_cost = jnp.where(no_solution, 0.0, cost)
    return words, applied_cost, no_solution, chosen, srv, skipped
