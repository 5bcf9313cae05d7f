"""Pallas TPU kernel: path latency h(p, r, rho) (paper Eqns 1-2).

This is the replication algorithm's analysis hot loop: the paper's Table 4
runtimes are dominated by evaluating the latency of millions-to-billions of
causal access paths against the current replication scheme.  The kernel
evaluates a block of paths per grid step entirely in VMEM.

Layout (TPU-native):  the *path* dimension is the 128-wide lane axis, so
every op in the position loop is a full-width vector op, and every per-path
vector is a ``(1, bP)`` row:

  home  int32  [L, bP]     home server of the object at each position
                           (-1 padded); bP = 128-aligned path block
  masks uint32 [L*W, bP]   packed replica-location words, row x*W + w is
                           word w of position x (W = ceil(S/32) words, bit
                           s of word w set iff a copy lives on server 32w+s)
  lens  int32  [1, bP]     path lengths
  out   int32  [1, bP]     distributed traversals per path

Per position i (fori_loop, vectorized across the 128 path lanes; rows are
read from the refs with ``pl.ds(i, 1)``):
  local  = bit test of masks[i] at the current server
  server = local ? server : home[i]
  cost  += valid(i) & ~local

The word select is a W-way static unroll of lane-wise `where` — no
gather needed, and W <= 16 for 512 servers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK = 128


def to_rows(x, block: int, fill=0):
    """[P, ...] -> [prod(...), Pp] lane-major rows, P padded to ``block``."""
    pad = (-x.shape[0]) % block
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                    constant_values=fill)
    return x.reshape(x.shape[0], -1).T


def _kernel(home_ref, mask_ref, len_ref, out_ref):
    L = home_ref.shape[0]
    W = mask_ref.shape[0] // L
    lens = len_ref[...]                          # [1, bP]
    server0 = jnp.maximum(home_ref[0:1, :], 0)

    def body(i, carry):
        server, cost = carry
        valid = (i < lens) & (lens > 0)
        widx = server >> 5                       # server >= 0 throughout
        bit = (server & 31).astype(jnp.uint32)
        word = jnp.zeros_like(server, jnp.uint32)
        for w in range(W):                       # static unroll (W small)
            word = jnp.where(widx == w, mask_ref[pl.ds(i * W + w, 1), :], word)
        local = ((word >> bit) & jnp.uint32(1)) != 0
        home_i = jnp.maximum(home_ref[pl.ds(i, 1), :], 0)
        nxt = jnp.where(valid, jnp.where(local, server, home_i), server)
        cost = cost + (valid & ~local).astype(jnp.int32)
        return nxt, cost

    _, cost = jax.lax.fori_loop(
        1, L, body, (server0, jnp.zeros_like(server0)))
    out_ref[...] = cost


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def path_latency_pallas(
    home: jnp.ndarray,    # int32 [P, L]  home server per position (-1 pad)
    masks: jnp.ndarray,   # uint32 [P, L, W]  packed replica words
    lengths: jnp.ndarray,  # int32 [P]
    *,
    interpret: bool,
    block: int = DEFAULT_BLOCK,
) -> jnp.ndarray:
    """Distributed-traversal count per path; see module docstring.

    Host-side API keeps the natural [P, L] layout; the kernel uses the
    lane-transposed layout.  ``interpret`` comes from
    ``repro.kernels.interpret_pallas`` (True off the TPU).
    """
    P, L = home.shape
    W = masks.shape[2]
    home_t = to_rows(home, block, fill=-1)       # [L, Pp]
    masks_t = to_rows(masks, block)              # [L*W, Pp]
    lens_t = to_rows(lengths, block)             # [1, Pp]
    Pp = home_t.shape[1]

    out = pl.pallas_call(
        _kernel,
        grid=(Pp // block,),
        in_specs=[
            pl.BlockSpec((L, block), lambda p: (0, p)),
            pl.BlockSpec((L * W, block), lambda p: (0, p)),
            pl.BlockSpec((1, block), lambda p: (0, p)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda p: (0, p)),
        out_shape=jax.ShapeDtypeStruct((1, Pp), jnp.int32),
        interpret=interpret,
    )(home_t, masks_t, lens_t)
    return out[0, :P]
