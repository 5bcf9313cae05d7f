"""Pallas TPU kernel: policy-routed access walk (Eqn 1 + RoutingPolicy).

The twin of ``repro.kernels.path_latency`` for the policy-parameterized
walk (``repro.engine.routing``): instead of hardcoding ``home[obj]`` as
every remote hop's target, the kernel picks the target from the object's
packed holder words — least-loaded alive copy holder within the preferred
candidate class (holders of the *next* object first when ``lookahead``),
home winning ties, then lowest id.  It also returns the full per-position
trace (visited server + locality), which the serving layers decorate.

Layout (TPU-native, as in ``path_latency``): the *path* dimension is the
128-wide lane axis and per-path vectors are ``(1, bP)`` rows.

  home  int32  [L, bP]     per-position routing target (-1 padded)
  masks uint32 [L*W, bP]   packed replica-location words, row x*W + w
  lens  int32  [1, bP]     path lengths
  start int32  [1, bP]     per-path start server
  load  f32    [Sp, 1]     per-server queue depths, Sp = W*32 (bits past
                           n_servers are never set, so the pad is inert)
  out   int32  [L, bP] x2  visited server / locality per position

Per position the holder bits are unpacked to an [Sp, bP] plane and the
candidate argmin reduces over the sublane axis — every op is a full-width
vector op across the path lanes.  Positions are read from the refs with
``pl.ds(i, 1)`` and the trace rows are stored the same way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.path_latency import DEFAULT_BLOCK, to_rows


def _any0(x):
    """Sublane-axis ``any`` of a bool plane -> bool ``(1, bP)`` row."""
    return jnp.max(x.astype(jnp.int32), axis=0, keepdims=True) > 0


def _holder_bits(mask_ref, i, W: int):
    """Position ``i``'s holder bits: bool [W*32, bP] from its W word rows."""
    shifts = jax.lax.broadcasted_iota(jnp.int32, (32, 1), 0).astype(jnp.uint32)
    planes = [
        (mask_ref[pl.ds(i * W + w, 1), :] >> shifts) & jnp.uint32(1)
        for w in range(W)
    ]
    bits = planes[0] if W == 1 else jnp.concatenate(planes, axis=0)
    return bits != 0


def _pick(cand, home, score, iota_s):
    """Best-scoring candidate per lane; home wins ties, then lowest id.

    ``cand`` bool [Sp, bP], ``home`` int32 [1, bP], ``score`` f32 [Sp, 1]
    (one shared rank per server) or f32 [Sp, bP] (a per-lane score plane —
    the DP cost-to-go of ``nearest_copy_dp``).  Returns (target int32
    [1, bP] — garbage where no candidate —, any bool [1, bP]); the scalar
    twins are ``repro.engine.routing.pick_holder_host`` /
    ``pick_holder_scored``.
    """
    lv = jnp.where(cand, score, jnp.inf)
    m = jnp.min(lv, axis=0, keepdims=True)
    best = cand & (lv <= m)
    home_ok = _any0(best & (iota_s == jnp.maximum(home, 0))) & (home >= 0)
    first = jnp.min(jnp.where(best, iota_s, iota_s.shape[0]), axis=0,
                    keepdims=True)
    return jnp.where(home_ok, home, first), _any0(cand)


def _hop(i, server, lens, home_ref, mask_ref, score, iota_s, *, W: int,
         home_first: bool = False, lookahead: bool = False):
    """One step of the walk at position ``i`` for every lane.

    Returns (next server, local, valid) as ``(1, bP)`` rows; ``score`` is
    the pick's rank (``_pick``), unused when ``home_first``.
    """
    L = home_ref.shape[0]
    valid = i < lens
    bits = _holder_bits(mask_ref, i, W)
    local = _any0(bits & (iota_s == jnp.maximum(server, 0))) & (server >= 0)
    h_i = home_ref[pl.ds(i, 1), :]
    if home_first:
        tgt = h_i
    else:
        tgt, any_c = _pick(bits, h_i, score, iota_s)
        tgt = jnp.where(any_c, tgt, -1)
        if lookahead:
            nxt_ok = (i + 1) < lens
            nbits = _holder_bits(mask_ref, jnp.minimum(i + 1, L - 1), W)
            la = bits & nbits & nxt_ok
            la_tgt, la_any = _pick(la, h_i, score, iota_s)
            tgt = jnp.where(la_any, la_tgt, tgt)
    nxt = jnp.where(local, server, tgt).astype(jnp.int32)
    return jnp.where(valid, nxt, server), local, valid


def _make_kernel(W: int, lookahead: bool, home_first: bool, scored: bool):
    """The routed walk; ``scored`` ranks holders by a per-position score
    plane (the ``nearest_copy_dp`` twin) instead of a shared load column."""
    Sp = W * 32

    def kernel(home_ref, mask_ref, len_ref, start_ref, score_ref,
               srv_ref, loc_ref):
        L = home_ref.shape[0]
        lens = len_ref[...]                      # [1, bP]
        iota_s = jax.lax.broadcasted_iota(jnp.int32, (Sp, lens.shape[1]), 0)
        load = None if scored else score_ref[...]  # [Sp, 1]

        valid0 = lens > 0
        server0 = jnp.where(valid0, start_ref[...], 0).astype(jnp.int32)
        srv_ref[0:1, :] = server0                # rows 1.. are the loop's
        loc_ref[0:1, :] = valid0.astype(jnp.int32)

        def body(i, server):
            if scored:
                score = score_ref[pl.ds(pl.multiple_of(i * Sp, Sp), Sp), :]
            else:
                score = load
            nxt, local, valid = _hop(
                i, server, lens, home_ref, mask_ref, score, iota_s, W=W,
                home_first=home_first, lookahead=lookahead,
            )
            srv_ref[pl.ds(i, 1), :] = nxt
            loc_ref[pl.ds(i, 1), :] = (local & valid).astype(jnp.int32)
            return nxt

        jax.lax.fori_loop(1, L, body, server0)

    return kernel


def _walk_call(home, masks, lengths, start, score, *, block, interpret,
               lookahead, home_first, scored):
    """``score``: the load column [Sp, 1], or (``scored``) the score rows
    [L*Sp, Pp]."""
    P, L = home.shape
    W = masks.shape[2]
    home_t = to_rows(home, block, fill=-1)         # [L, Pp]
    Pp = home_t.shape[1]
    score_spec = (
        pl.BlockSpec((score.shape[0], block), lambda p: (0, p)) if scored
        else pl.BlockSpec(score.shape, lambda p: (0, 0))
    )
    srv, loc = pl.pallas_call(
        _make_kernel(W, lookahead, home_first, scored),
        grid=(Pp // block,),
        in_specs=[
            pl.BlockSpec((L, block), lambda p: (0, p)),
            pl.BlockSpec((L * W, block), lambda p: (0, p)),
            pl.BlockSpec((1, block), lambda p: (0, p)),
            pl.BlockSpec((1, block), lambda p: (0, p)),
            score_spec,
        ],
        out_specs=[
            pl.BlockSpec((L, block), lambda p: (0, p)),
            pl.BlockSpec((L, block), lambda p: (0, p)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L, Pp), jnp.int32),
            jax.ShapeDtypeStruct((L, Pp), jnp.int32),
        ],
        interpret=interpret,
        name="repro_scored_walk" if scored else "repro_routed_walk",
    )(home_t, to_rows(masks, block), to_rows(lengths, block),
      to_rows(start, block), score)
    return srv.T[:P], loc.T[:P].astype(bool)


@functools.partial(
    jax.jit,
    static_argnames=("block", "interpret", "lookahead", "home_first"),
)
def routed_walk_pallas(
    home: jnp.ndarray,     # int32 [P, L]  per-position target (-1 pad)
    masks: jnp.ndarray,    # uint32 [P, L, W]  packed replica words
    lengths: jnp.ndarray,  # int32 [P]
    start: jnp.ndarray,    # int32 [P]  start server per path
    load: jnp.ndarray,     # float32 [W*32]  per-server queue depths
    *,
    interpret: bool,
    block: int = DEFAULT_BLOCK,
    lookahead: bool = True,
    home_first: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(servers int32 [P, L], local bool [P, L]); see module docstring."""
    return _walk_call(
        home, masks, lengths, start, load.reshape(-1, 1), block=block,
        interpret=interpret, lookahead=lookahead, home_first=home_first,
        scored=False,
    )


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def scored_walk_pallas(
    home: jnp.ndarray,     # int32 [P, L]  per-position target (-1 pad)
    masks: jnp.ndarray,    # uint32 [P, L, W]  packed replica words
    lengths: jnp.ndarray,  # int32 [P]
    start: jnp.ndarray,    # int32 [P]  start server per path
    scores: jnp.ndarray,   # float32 [P, L, W*32]  per-position hop scores
    *,
    interpret: bool,
    block: int = DEFAULT_BLOCK,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(servers int32 [P, L], local bool [P, L]); scored-pick walk.

    Identical to the routed walk except the remote-hop pick ranks holders
    by a per-(position, server, path) score plane (the suffix-DP
    cost-to-go, precomputed on device) instead of a shared load vector.
    """
    return _walk_call(
        home, masks, lengths, start, to_rows(scores, block),
        block=block, interpret=interpret,
        lookahead=False, home_first=False, scored=True,
    )
