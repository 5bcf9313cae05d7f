"""Unified device-resident latency-evaluation engine.

One backend-dispatched implementation of the paper's hot primitive —
h(p, r, rho), the distributed-traversal count of a path under a
replication scheme (Eqns 1-3) — shared by the greedy UPDATE driver, the
exact reference, the baselines, the distsys executor, the workload
analyzer, and every benchmark.

  LatencyEngine  — path_latencies / query_latencies / query_slack /
                   is_feasible / margin_costs behind
                   "reference" | "jnp" | "pallas"; latency constraints are
                   vector-valued (per-query t_Q, scalar broadcast as the
                   degenerate case)
  RawScheme      — minimal mask+shard scheme carrier (from_arrays input)
  PackedScheme   — the device-resident packed uint32 bitmask state
  RoutingPolicy  — pluggable remote-hop target selection for the batched
                   access walk (home_first | nearest_copy | queue_aware |
                   nearest_copy_dp(k), the suffix-DP lookahead family);
                   consumed by access_trace / path_latencies(policy=)
                   and the policy-aware greedy provisioning gate
  TRANSFER       — host<->device transfer accounting (perf benchmarks);
                   ``to_device`` / ``to_host`` are the counted paths
  PathStream     — streamed PathSet ingestion from a host generator with
                   peak-residency accounting (provisioning at scale);
                   consumed by ``repro.core.greedy.replicate_stream``
  PathIndex      — CSR object->path inverted index; backs the engine's
                   persistent dirty-set latency cache
                   (``path_latencies(..., incremental=True)``) and the
                   prune sweep's affected-path lookups
  KResilient     — k-resilience constraint (loss cases over servers or
                   fault domains); consumed by
                   ``LatencyEngine.resilient_path_latencies`` /
                   ``is_resilient_feasible`` and the greedy gate
                   (``replicate_workload(resilience=...)``)
"""
from repro.engine.engine import DevicePaths, LatencyEngine, RawScheme
from repro.engine.incremental import IncrementalEval, PathIndex
from repro.engine.resilience import (
    KResilient,
    case_word_mask,
    failover_shard,
    resolve_resilience,
)
from repro.engine.sharding import round_up_rows
from repro.engine.packed import PackedScheme, pack_bool_mask, unpack_words
from repro.engine.routing import (
    POLICIES,
    HomeFirst,
    NearestCopy,
    NearestCopyDP,
    QueueAware,
    RoutingPolicy,
    nearest_copy_dp,
    resolve_policy,
)
from repro.engine.streaming import (
    TRANSFER,
    PathStream,
    StreamStats,
    double_buffer,
    to_device,
    to_host,
)
from repro.engine.backends import BACKENDS

__all__ = [
    "PathStream",
    "StreamStats",
    "LatencyEngine",
    "DevicePaths",
    "RawScheme",
    "PackedScheme",
    "pack_bool_mask",
    "unpack_words",
    "TRANSFER",
    "to_device",
    "to_host",
    "double_buffer",
    "BACKENDS",
    "POLICIES",
    "RoutingPolicy",
    "HomeFirst",
    "NearestCopy",
    "NearestCopyDP",
    "QueueAware",
    "nearest_copy_dp",
    "resolve_policy",
    "PathIndex",
    "IncrementalEval",
    "round_up_rows",
    "KResilient",
    "case_word_mask",
    "failover_shard",
    "resolve_resilience",
]
