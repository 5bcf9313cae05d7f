"""Jit'd public wrappers for the Pallas kernels.

Selects interpret mode automatically (Pallas executes the kernel body in
Python on CPU; compiled Mosaic on TPU), and adapts framework-level data
structures (PathSet + ReplicationScheme) to kernel inputs.
"""
from __future__ import annotations

import numpy as np

from repro.kernels import interpret_pallas
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.flash_prefill import flash_prefill_pallas


def path_latency(pathset, scheme, block: int = 128) -> np.ndarray:
    """Kernel-backed h(p, r, rho) for a PathSet + ReplicationScheme.

    Thin wrapper over the unified engine's ``pallas`` backend: the packed
    scheme is uploaded once and the kernel inputs (home servers + replica
    words per position) are gathered on device, instead of the former
    host-side ``[P, L, W]`` gather + transfer.
    """
    from repro.engine import LatencyEngine  # lazy: keep kernels importable alone

    eng = LatencyEngine(scheme, backend="pallas", block=block)
    return eng.path_latencies(pathset)


def decode_attention(q, k, v, lengths, block_t: int = 256):
    """Flash-decode GQA attention (see kernels.decode_attention)."""
    return decode_attention_pallas(
        q, k, v, lengths, block_t=block_t, interpret=interpret_pallas())


def embedding_bag(table, ids, mode: str = "mean"):
    """TBE-style embedding bag (see kernels.embedding_bag)."""
    return embedding_bag_pallas(table, ids, mode=mode,
                                interpret=interpret_pallas())


def flash_prefill(q, k, v, block_q: int = 128, block_k: int = 128,
                  window: int = 0):
    """Causal flash attention for prefill (see kernels.flash_prefill)."""
    return flash_prefill_pallas(q, k, v, block_q=block_q, block_k=block_k,
                                window=window, interpret=interpret_pallas())
