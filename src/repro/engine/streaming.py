"""Host<->device transfer accounting + double-buffered chunk streaming.

Every host->device transfer the engine performs goes through ``to_device``
and every blocking device->host readback of the greedy provisioner
through ``to_host``, so the byte counters (``TRANSFER``) reflect real
traffic and, with the telemetry plane on, the counter
``repro.engine.d2h_calls`` counts its host round trips; the perf
benchmarks (``benchmarks/perf_iterate.py engine`` and
``benchmarks/engine_backends.py``) read it to track the packed-resident
path's transfer advantage over the legacy per-call bool-mask uploads.
``h2d_bytes`` counts *payload* bytes only — alignment padding a caller
appends to hit a fixed jit shape is tracked separately in
``padded_bytes`` (it rides the same copy, but it is not workload data, and
folding it into the payload counter made the final partial chunk look more
expensive than the data it carried).

``stream_chunks`` is the engine's evaluation pipeline: while chunk ``i``
computes on device (JAX dispatch is asynchronous), chunk ``i + 1``'s
host->device copy is already enqueued — a two-deep software pipeline that
replaces the old synchronous per-chunk ``jnp.asarray`` + ``np.asarray``
round trip.  The final chunk is padded to the full chunk shape so every
step hits the same jit cache entry.

``PathStream`` is the provisioning-scale ingestion contract: a host
generator of :class:`~repro.core.paths.PathSet` chunks, consumed once,
with peak-residency accounting — the greedy driver
(``repro.core.greedy.replicate_stream``) provisions against it without
the full path set ever being host-resident.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Iterable, Iterator, Sequence

import jax.numpy as jnp
import numpy as np

from repro import obs


@dataclasses.dataclass
class TransferStats:
    h2d_bytes: int = 0
    h2d_calls: int = 0
    d2h_bytes: int = 0
    # alignment-pad bytes appended by callers to hit a fixed jit shape;
    # they cross the bus but carry no workload data (kept out of
    # h2d_bytes so the perf benchmarks' byte assertions stay exact)
    padded_bytes: int = 0
    # bytes uploaded for incremental dirty-set evaluation (the compacted
    # dirty-row index vectors of ``repro.engine.incremental``): a subset
    # of h2d_bytes, broken out so the incremental path's transfer savings
    # are visible next to what a full re-upload would have cost
    gathered_bytes: int = 0

    def reset(self) -> None:
        self.h2d_bytes = 0
        self.h2d_calls = 0
        self.d2h_bytes = 0
        self.padded_bytes = 0
        self.gathered_bytes = 0

    def snapshot(self) -> dict:
        return {
            "h2d_bytes": self.h2d_bytes,
            "h2d_calls": self.h2d_calls,
            "d2h_bytes": self.d2h_bytes,
            "padded_bytes": self.padded_bytes,
            "gathered_bytes": self.gathered_bytes,
        }

    @contextlib.contextmanager
    def scope(self):
        """Isolate a region's transfer accounting, preserving outer totals.

        On entry the counters reset to zero, so assertions inside the
        block see only the block's own traffic; on exit the pre-entry
        values are added back, so the process-level totals equal
        outer + inner as if the scope had never existed.  Nests cleanly —
        each level isolates its own deltas.  This replaces the old
        reset-around-every-test fixture: a benchmark ENTRY or a test gets
        clean counters without silently zeroing someone else's.
        """
        saved = self.snapshot()
        self.reset()
        try:
            yield self
        finally:
            self.h2d_bytes += saved["h2d_bytes"]
            self.h2d_calls += saved["h2d_calls"]
            self.d2h_bytes += saved["d2h_bytes"]
            self.padded_bytes += saved["padded_bytes"]
            self.gathered_bytes += saved["gathered_bytes"]


TRANSFER = TransferStats()


def to_device(x, payload_bytes: int | None = None) -> jnp.ndarray:
    """Counted host->device transfer (the only upload path in the engine).

    ``payload_bytes`` marks how many of the array's bytes are real data;
    the remainder (alignment padding) is booked under
    ``TRANSFER.padded_bytes`` instead of ``h2d_bytes``.
    """
    a = np.asarray(x)
    payload = a.nbytes if payload_bytes is None else int(payload_bytes)
    TRANSFER.h2d_bytes += payload
    TRANSFER.padded_bytes += a.nbytes - payload
    TRANSFER.h2d_calls += 1
    return jnp.asarray(a)


def to_host(x) -> np.ndarray:
    """Counted device->host readback: ``np.asarray(x)``, which blocks until
    ``x`` is computed, plus its bytes in ``TRANSFER.d2h_bytes`` and, with
    the telemetry plane on, one ``repro.engine.d2h_calls``."""
    a = np.asarray(x)
    TRANSFER.d2h_bytes += a.nbytes
    if obs.enabled():
        obs.REGISTRY.counter("repro.engine.d2h_calls").inc()
    return a


def stream_chunks(
    arrays: Sequence[np.ndarray],
    n: int,
    chunk: int,
    compute: Callable,
    pad_values: Sequence[int],
    align: int = 128,
) -> list:
    """Double-buffered map of ``compute`` over row-chunks of ``arrays``.

    ``arrays`` are host arrays sharing leading dimension ``n``.  Full
    chunks have exactly ``chunk`` rows; the final partial chunk is padded
    up to a multiple of ``align`` with ``pad_values`` (one per array), so
    a call compiles at most two shapes.  Pad rows are accounted as
    ``TRANSFER.padded_bytes``, not payload.  Returns the list of *device*
    outputs (callers concatenate / read back once at the end, keeping
    dispatch async).
    """
    if n == 0:
        return []

    def put(start: int):
        stop = min(start + chunk, n)
        rows = stop - start
        target = chunk if rows == chunk else -(-rows // align) * align
        out = []
        for a, pv in zip(arrays, pad_values):
            piece = a[start:stop]
            payload = piece.nbytes
            if rows < target:
                pad = np.full((target - rows,) + a.shape[1:], pv, a.dtype)
                piece = np.concatenate([piece, pad], axis=0)
            out.append(to_device(piece, payload_bytes=payload))
        return tuple(out)

    starts = list(range(0, n, chunk))
    outs = []
    nxt = put(starts[0])
    for i, start in enumerate(starts):
        cur = nxt
        out = compute(*cur)  # async dispatch; device starts computing
        if i + 1 < len(starts):
            nxt = put(starts[i + 1])  # upload overlaps the in-flight compute
        outs.append(out)
    return outs


def double_buffer(items: Iterable, dispatch: Callable) -> float:
    """Two-deep pipeline over a lazy producer: overlap ingest with compute.

    ``dispatch(item)`` must *enqueue* device work and return without
    blocking (JAX dispatch is asynchronous as long as nothing reads a
    device value back).  While that work is in flight, the next item is
    pulled from ``items`` — so a generator producer materializes chunk
    ``i + 1`` on the host during chunk ``i``'s device compute, the same
    pipeline shape as :func:`stream_chunks` but for callers that own
    their dispatch (``repro.core.greedy.replicate_stream``).

    Returns the host seconds of producer work that overlapped in-flight
    device work (the pipeline's win over a strict pull-then-dispatch
    loop); the first item's materialization has nothing to hide behind
    and is not counted.
    """
    it = iter(items)
    try:
        cur = next(it)
    except StopIteration:
        return 0.0
    overlap_s = 0.0
    while True:
        dispatch(cur)
        t0 = time.perf_counter()
        try:
            cur = next(it)  # producer runs while the device computes
        except StopIteration:
            return overlap_s
        overlap_s += time.perf_counter() - t0


@dataclasses.dataclass
class StreamStats:
    """Residency accounting of one :class:`PathStream` consumption."""

    total_paths: int = 0
    chunks: int = 0
    peak_resident_paths: int = 0
    # host seconds of chunk materialization hidden behind device compute
    # (filled by pipelined consumers; 0.0 for a strict pull-then-compute)
    ingest_overlap_s: float = 0.0
    # candidate-table residency (filled by replicate_stream): the largest
    # host block of C(h, t) selection rows ever materialized at once vs.
    # the total rows shipped — peak < total proves the deep-path table
    # construction streamed instead of landing whole on the host
    peak_resident_table_rows: int = 0
    total_table_rows: int = 0


class PathStream:
    """Streamed PathSet ingestion from a host generator (consumed once).

    Wraps an iterable of :class:`~repro.core.paths.PathSet` chunks — or
    ``(PathSet, per_path_budgets)`` tuples when the latency constraint
    varies within the stream — and records how many paths were ever
    host-resident at once (``stats.peak_resident_paths``): the contract
    the provisioning-scale benchmark asserts (peak < total for a genuine
    stream).  Iteration yields normalized ``(PathSet, budgets_or_None)``
    pairs; generators are consumed lazily, so the producer can build each
    chunk on demand and drop it after the yield.
    """

    def __init__(self, chunks: Iterable):
        self._chunks = chunks
        self._consumed = False
        self.stats = StreamStats()

    def __iter__(self) -> Iterator[tuple]:
        if self._consumed:
            raise RuntimeError("PathStream is single-use; build a new one")
        self._consumed = True
        for item in self._chunks:
            ps, t = item if isinstance(item, tuple) else (item, None)
            if ps.n_paths == 0:
                continue
            self.stats.total_paths += ps.n_paths
            self.stats.chunks += 1
            self.stats.peak_resident_paths = max(
                self.stats.peak_resident_paths, ps.n_paths
            )
            yield ps, t
