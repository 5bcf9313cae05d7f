"""Provisioning at production scale: fused megakernel vs separate dispatch.

Two arms build the same scheme on the SNB drift union (``n_servers=6``,
``t=1``, ``nearest_copy`` pricing, prune included):

  ``separate``  the PR-5 pipeline — per batch, a host-driven routed-gate
                dispatch, the UPDATE dispatch, and three blocking stat
                readbacks.
  ``fused``     one ``_fused_update_batch`` jit step per batch (gate +
                candidate scoring + bit-test + scatter-OR in a single
                dispatch, stats reduced on device).

Both arms end with the same batched independent-group prune (one
dispatch per group).  Both are run twice and the second (warm) run is
timed, so the comparison excludes jit compilation; the speedup is
reported, and so is the prune's batching: candidates per gate dispatch,
counted by the telemetry plane (1 would be a per-candidate sweep).
Asserted, not just reported:

  * the two arms produce **bit-identical** schemes (pre- and post-prune);
  * the servers x paths scale grid tops out at ``n_servers=128`` x
    >= 100k synthetic paths provisioned through **streamed ingestion**
    (``replicate_stream``), with peak host-resident paths < the total
    path count (the PathStream residency contract).

Usage: PYTHONPATH=src python -m benchmarks.provisioning_scale [--smoke] [out.json]
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

from benchmarks.common import emit
from repro.core.greedy import replicate_stream, replicate_workload
from repro.core.paths import PathSet
from repro.core.replication import prune_scheme_replicas
from repro.engine import PathStream
from repro.graph import make_sharding, snb_like
from repro.serve import snb_drift

N_SERVERS = 6
T = 1
POLICY = "nearest_copy"
# tier-1 guard budget for the default (smoke) grid point, cold compile
# included — tests/test_provision_scale.py fails loudly past this
DEFAULT_BUDGET_S = 120.0

STREAM_CHUNK = 8192
SCALE_PATH_LEN = 6


def snb_union(smoke: bool):
    """The PR-5 benchmark workload: SNB drift phases, concatenated."""
    q = 120 if smoke else 320
    snb = snb_like(1, seed=0)
    g = snb.graph
    f = g.object_sizes().astype(np.float32)
    shard = make_sharding("hash", g, N_SERVERS, seed=0)
    phases = snb_drift(snb, n_phases=3, queries_per_phase=q, hot_prob=0.9,
                       seed=0)
    union = PathSet.concatenate([p.pathset for p in phases])
    return union, shard, f


def run_pipeline(union, shard, f, fused: bool):
    """One provisioning pipeline end-to-end; returns (mask, seconds)."""
    t0 = time.perf_counter()
    scheme, _ = replicate_workload(
        union, shard, N_SERVERS, t=T, f=f, policy=POLICY,
        policy_prune=False, fused=fused,
    )
    prune_scheme_replicas(scheme, union, T, policy=POLICY, f=f)
    return scheme.mask, time.perf_counter() - t0


def prune_batching(union, shard, f):
    """The prune's (candidates, gate dispatches) in one fused pipeline,
    from the telemetry plane's counters."""
    from repro import obs

    names = ("repro.greedy.prune.candidates", "repro.greedy.prune.dispatches")
    before = obs.REGISTRY.snapshot()
    was = obs.enabled()
    obs.enable()
    try:
        run_pipeline(union, shard, f, fused=True)
    finally:
        (obs.enable if was else obs.disable)()
    after = obs.REGISTRY.snapshot()
    return tuple(int(after.get(n, 0) - before.get(n, 0)) for n in names)


def default_grid_point():
    """The tier-1 guard target: smoke union, fused arm, cold compile.

    Returns (runtime_s, mask); the guard asserts runtime < DEFAULT_BUDGET_S.
    """
    union, shard, f = snb_union(smoke=True)
    mask, secs = run_pipeline(union, shard, f, fused=True)
    return secs, mask


def synthetic_stream(n_paths: int, n_objects: int, seed: int,
                     chunk: int = STREAM_CHUNK):
    """Zipf-skewed fixed-length synthetic paths, yielded chunk-by-chunk.

    A generator — each chunk is materialized on demand and dropped after
    the yield, so host residency peaks at ``chunk`` paths.
    """
    rng = np.random.default_rng(seed)
    L = SCALE_PATH_LEN
    for start in range(0, n_paths, chunk):
        rows = min(chunk, n_paths - start)
        # zipf-ish skew: low object ids are hot (drift hotsets at scale)
        raw = rng.zipf(1.3, size=(rows, L)).astype(np.int64)
        objects = ((raw - 1) % n_objects).astype(np.int32)
        lengths = np.full(rows, L, np.int32)
        yield PathSet(objects, lengths, np.arange(rows, dtype=np.int32))


def run_scale_point(n_servers: int, n_paths: int, smoke: bool):
    """One streamed grid point; returns the result row (asserts residency)."""
    n_objects = max(4 * n_servers, n_paths // 8)
    shard = (np.arange(n_objects) % n_servers).astype(np.int32)
    stream = PathStream(synthetic_stream(n_paths, n_objects, seed=n_servers))
    t0 = time.perf_counter()
    scheme, stats = replicate_stream(
        stream, shard, n_servers, t=T, fused=True,
        batch_size=1024, prune=False,
    )
    secs = time.perf_counter() - t0
    assert stats.peak_resident_paths < stats.paths_processed, (
        f"streamed ingestion held {stats.peak_resident_paths} paths "
        f"host-resident out of {stats.paths_processed} — not a stream"
    )
    assert stats.failed_paths == 0
    return {
        "n_servers": n_servers,
        "n_paths": int(stats.paths_processed),
        "peak_resident_paths": int(stats.peak_resident_paths),
        "chunks": stream.stats.chunks,
        "replicas": int(stats.replicas),
        "runtime_s": round(secs, 2),
        "paths_per_s": round(stats.paths_processed / max(secs, 1e-9), 1),
    }


def run(out_path: str = "BENCH_scale.json", smoke: bool = False) -> dict:
    result: dict = {
        "t": T,
        "policy": POLICY,
        "smoke": smoke,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }

    # -- fused vs separate on the SNB union (bit-identical + speedup) ------
    union, shard, f = snb_union(smoke)
    result["union_paths"] = union.n_paths
    arms = {}
    for name, fused in (("separate", False), ("fused", True)):
        run_pipeline(union, shard, f, fused)          # warm (jit compile)
        mask, secs = run_pipeline(union, shard, f, fused)
        arms[name] = (mask, secs)
        emit("provisioning_scale", "runtime_s", round(secs, 3), arm=name,
             n_servers=N_SERVERS, paths=union.n_paths)
    assert np.array_equal(arms["separate"][0], arms["fused"][0]), (
        "fused megakernel pipeline diverged from the separate-dispatch "
        "pipeline (schemes must be bit-identical)"
    )
    speedup = arms["separate"][1] / max(arms["fused"][1], 1e-9)
    cand, disp = prune_batching(union, shard, f)
    result["snb_union"] = {
        "separate_s": round(arms["separate"][1], 3),
        "fused_s": round(arms["fused"][1], 3),
        "speedup": round(speedup, 2),
        "bit_identical": True,
        "prune_candidates": cand,
        "prune_dispatches": disp,
        "prune_candidates_per_dispatch": round(cand / max(disp, 1), 2),
    }
    emit("provisioning_scale", "speedup", round(speedup, 2),
         n_servers=N_SERVERS, paths=union.n_paths)

    # -- servers x paths scale grid, streamed ingestion --------------------
    grid = [(16, 20_000), (128, 12_000)] if smoke else [
        (16, 20_000), (32, 50_000), (128, 100_000),
    ]
    result["scale_grid"] = []
    for n_servers, n_paths in grid:
        row = run_scale_point(n_servers, n_paths, smoke)
        result["scale_grid"].append(row)
        emit("provisioning_scale", "paths_per_s", row["paths_per_s"],
             n_servers=n_servers, paths=row["n_paths"])
    if not smoke:
        top = result["scale_grid"][-1]
        assert top["n_servers"] == 128 and top["n_paths"] >= 100_000, (
            "scale grid must top out at n_servers=128 x >=100k paths"
        )

    with open(out_path, "w") as fh:
        json.dump(result, fh, indent=2)
    print(f"# wrote {out_path}")
    return result


if __name__ == "__main__":
    args = list(sys.argv[1:])
    smoke = "--smoke" in args
    args = [a for a in args if a != "--smoke"]
    run(args[0] if args else "BENCH_scale.json", smoke=smoke)
