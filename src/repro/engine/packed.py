"""Device-resident packed replication state (the engine's source of truth).

The replication scheme is stored on device as uint32 bit-words
``words[v, w]``: bit ``s % 32`` of word ``s // 32`` is set iff object ``v``
has a copy at server ``s``.  All engine backends evaluate the access
function (paper Eqn 1) against these words; monotone 0->1 updates are
applied on-device with donated buffers (``scatter_or_pairs``), so the
unpacked ``[n_objects, n_servers]`` bool mask never crosses the host
boundary after construction.

Layout notes
------------
``words`` carries one *sacrificial* extra row (index ``n_objects``):
vectorized callers route masked-out updates there instead of predicating,
mirroring the padded-row trick the greedy UPDATE kernel uses.  Packing is
little-endian within a word (server ``32w`` is bit 0 of word ``w``), the
same layout ``repro.kernels.path_latency`` consumes.

This module intentionally depends only on numpy/JAX (no ``repro.core``
imports) so it can sit below both the core algorithms and the kernels.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.engine.streaming import to_device, to_host


def n_words(n_servers: int) -> int:
    """Number of uint32 words needed for ``n_servers`` membership bits."""
    return (n_servers + 31) // 32


def pack_bool_mask(mask: np.ndarray) -> np.ndarray:
    """Host-side pack: bool [R, S] -> uint32 [R, ceil(S/32)].

    Byte-wise: each row's bits go into ``ceil(S/8)`` little-endian bytes
    (``np.packbits``), placed at the front of the row's ``4 * W`` bytes
    and read as ``'<u4'`` words, so server ``s`` is bit ``s % 32`` of
    word ``s // 32`` on any host.  Accepts any array ``np.asarray`` makes
    bool (non-contiguous slices, 0/1 integers, no rows).
    """
    m = np.asarray(mask, dtype=bool)
    R, S = m.shape
    with obs.span("repro.engine.codec", op="pack", rows=R):
        W, B = n_words(S), -(-S // 8)
        if S != 8 * B:  # pad each row to whole bytes: one flat pack, not R
            padded = np.zeros((R, 8 * B), dtype=bool)
            padded[:, :S] = m
            m = padded
        packed = np.packbits(m.reshape(-1), bitorder="little").reshape(R, B)
        out = np.zeros((R, 4 * W), dtype=np.uint8)
        out[:, :B] = packed
        return out.view("<u4")


def unpack_words(words: np.ndarray, n_servers: int) -> np.ndarray:
    """Host-side unpack: uint32 [R, W] -> bool [R, n_servers] (a new,
    writable, C-contiguous array; the inverse of :func:`pack_bool_mask`)."""
    R, W = words.shape
    with obs.span("repro.engine.codec", op="unpack", rows=R):
        b = np.ascontiguousarray(words, dtype="<u4").view(np.uint8)
        return np.unpackbits(b.reshape(R, 4 * W), axis=1, count=n_servers,
                             bitorder="little").view(bool)


# ---------------------------------------------------------------------------
# Traceable primitives (usable inside other jits, e.g. the greedy UPDATE).
# ---------------------------------------------------------------------------
def test_bits(words: jnp.ndarray, objects: jnp.ndarray, servers: jnp.ndarray):
    """Membership bit-test against the packed words (traceable).

    ``objects`` and ``servers`` broadcast against each other; both must be
    pre-clamped to valid ranges.  Returns bool of the broadcast shape.
    """
    word = words[objects, servers // 32]
    bit = (servers % 32).astype(jnp.uint32)
    return ((word >> bit) & jnp.uint32(1)).astype(jnp.bool_)


def _scatter_bits(
    words: jnp.ndarray, objects: jnp.ndarray, servers: jnp.ndarray, on: bool
) -> jnp.ndarray:
    """Set (``on``) or clear (object, server) membership bits (traceable).

    Deterministic under duplicate pairs: the update is bit-sliced into 32
    static rounds; within a round every duplicate write to a cell carries
    the identical value.  Pairs with a negative object or server — and the
    sacrificial row itself — are routed to the sacrificial last row, so
    callers can mask by index instead of compacting.  The rounds scatter
    into the words flattened to one axis: into the 2-D ``[n + 1, W]``
    array the TPU compiler re-lays the whole array out (padded to 128
    lanes) once per round.
    """
    pad_row, W = words.shape[0] - 1, words.shape[1]
    ok = (objects >= 0) & (servers >= 0) & (objects < pad_row)
    cell = jnp.where(ok, objects * W + servers // 32, pad_row * W).reshape(-1)
    b_idx = jnp.where(ok, servers % 32, 0).reshape(-1)
    flat = words.reshape(-1)
    for b in range(32):
        i = jnp.where(b_idx == b, cell, pad_row * W)
        bit = jnp.uint32(1 << b)
        flat = flat.at[i].set(flat[i] | bit if on else flat[i] & ~bit)
    return flat.reshape(words.shape)


def scatter_or_pairs(
    words: jnp.ndarray, objects: jnp.ndarray, servers: jnp.ndarray
) -> jnp.ndarray:
    """Monotone scatter-OR of (object, server) pairs into the packed words
    (see :func:`_scatter_bits`)."""
    return _scatter_bits(words, objects, servers, True)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_or_jit(words, objects, servers):
    return scatter_or_pairs(words, objects, servers)


def scatter_clear_pairs(
    words: jnp.ndarray, objects: jnp.ndarray, servers: jnp.ndarray
) -> jnp.ndarray:
    """Clear (object, server) membership bits (the prune-sweep inverse; see
    :func:`_scatter_bits`).  Removals are NOT monotone — callers that
    cached derived state (bool masks, engines) must refresh it.
    """
    return _scatter_bits(words, objects, servers, False)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_clear_jit(words, objects, servers):
    return scatter_clear_pairs(words, objects, servers)


@jax.jit
def _unpack_load_jit(words, f):
    """f_r(s) per server from packed words, entirely on device."""
    n = f.shape[0]
    W = words.shape[1]
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[:n, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
    mask = bits.reshape(n, W * 32).astype(jnp.float32)
    return f @ mask  # [W * 32]; caller slices [:n_servers]


@jax.jit
def _popcount_jit(words):
    n_rows = words.shape[0]
    v = words[: n_rows - 1]
    # SWAR popcount per word, summed.
    v = v - ((v >> 1) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> 2) & jnp.uint32(0x33333333))
    v = (v + (v >> 4)) & jnp.uint32(0x0F0F0F0F)
    return jnp.sum((v * jnp.uint32(0x01010101)) >> 24)


@dataclasses.dataclass
class PackedScheme:
    """Incrementally maintained device-resident replication scheme.

    Attributes:
      words: uint32 [n_objects + 1, W] on device (sacrificial last row).
      shard: int32 [n_objects] on device (the sharding function d).
      n_servers: membership bits in use per row.
    """

    words: jax.Array
    shard: jax.Array
    n_servers: int

    @property
    def n_objects(self) -> int:
        return self.words.shape[0] - 1

    @property
    def n_words(self) -> int:
        return self.words.shape[1]

    @classmethod
    def from_mask(cls, mask: np.ndarray, shard: np.ndarray) -> "PackedScheme":
        """One host-side pack + one (32x smaller) transfer."""
        n, S = mask.shape
        host = np.zeros((n + 1, n_words(S)), dtype=np.uint32)
        host[:n] = pack_bool_mask(np.asarray(mask, dtype=bool))
        return cls(
            words=to_device(host),
            shard=to_device(np.asarray(shard, dtype=np.int32)),
            n_servers=S,
        )

    @classmethod
    def from_sharding(cls, shard: np.ndarray, n_servers: int) -> "PackedScheme":
        n = shard.shape[0]
        host = np.zeros((n + 1, n_words(n_servers)), dtype=np.uint32)
        s = np.asarray(shard, dtype=np.int64)
        host[np.arange(n), s // 32] = np.uint32(1) << (s % 32).astype(np.uint32)
        return cls(
            words=to_device(host),
            shard=to_device(np.asarray(shard, dtype=np.int32)),
            n_servers=n_servers,
        )

    def add(self, objects, servers) -> None:
        """On-device monotone scatter-OR (donated buffer; words reassigned)."""
        self.words = _scatter_or_jit(
            self.words,
            to_device(np.asarray(objects, dtype=np.int32)),
            to_device(np.asarray(servers, dtype=np.int32)),
        )

    def remove(self, objects, servers) -> None:
        """On-device membership-bit clear (the prune sweep's inverse).

        NOT monotone: any derived state (unpacked masks, downstream
        engines built from this scheme) is stale after a remove.
        """
        self.words = _scatter_clear_jit(
            self.words,
            to_device(np.asarray(objects, dtype=np.int32)),
            to_device(np.asarray(servers, dtype=np.int32)),
        )

    def unpack(self) -> np.ndarray:
        """Host readback of the full bool mask: one d2h of the whole words,
        the sacrificial row dropped on the host (it may hold set bits)."""
        return unpack_words(to_host(self.words)[: self.n_objects],
                            self.n_servers)

    def storage_per_server(self, f: np.ndarray | None = None) -> np.ndarray:
        n = self.n_objects
        fv = np.ones((n,), np.float32) if f is None else np.asarray(f, np.float32)
        load = _unpack_load_jit(self.words, to_device(fv))
        return to_host(load)[: self.n_servers].astype(np.float64)

    def replica_count(self) -> int:
        return int(_popcount_jit(self.words)) - self.n_objects
