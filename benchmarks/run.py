"""Benchmark harness: one module per paper table/figure.

  fig2_traversals       — Fig 2a-2d (traversal CDFs, single-site cost)
  fig6_latency_tradeoff — Fig 6a-6f (latency/throughput/replication vs t)
  fig7_sharding         — Fig 7a-7d + Table 3 (sharding schemes, dangling)
  table4_runtime        — Table 4 (algorithm runtime) + kernel timing
  reshard_cost          — §5.4 incremental-update cost
  beyond_paper          — MoE expert + recsys hot-row replication
  engine_backends       — LatencyEngine backend/chunk/transfer micro-bench
  perf_iterate          — engine transfer profile (resident vs legacy h2d)
  serve_tail            — serving simulator p99 vs load + controller value
  tenant_frontier       — multi-tenant SLOs: vector-t frontier, per-tenant
                          p99 static vs arbitrating controller
  routing_policies      — hop-routing policies: p99 vs load x
                          {home_first, nearest_copy, queue_aware} +
                          nearest-copy replica pruning
  provisioning_policies — policy-aware greedy vs home-first(+prune):
                          shipped/resident replication bytes at equal
                          nearest_copy feasibility over drift sequences
  provisioning_scale    — fused UPDATE megakernel vs separate dispatch
                          (bit-identical, >= 5x) + servers x paths scale
                          grid with streamed ingestion
  incremental_eval      — dirty-set window re-checks vs full re-eval on
                          the controller drift-repair loop (bit-identical,
                          >= 4x warm speedup, dirty-fraction accounting)
  fault_resilience      — k-resilient provisioning vs exhaustive
                          single-server loss (3-backend parity), chaos
                          kill/revive violation windows static vs
                          controller-on, routing-table coordinator savings

Usage: PYTHONPATH=src python -m benchmarks.run [module ...]
Prints ``bench,metric,tags,value`` CSV.

The harness runs with the telemetry plane enabled (``repro.obs``) and the
jit compile hook installed; each module executes inside its own
``TRANSFER.scope()`` so the per-module transfer snapshot is the module's
own traffic.  At exit the global metrics registry plus the per-module
transfer snapshots land in ``BENCH_metrics.json`` — the nightly metrics
artifact that rides next to the other ``BENCH_*.json`` files.  Compiled
programs persist in ``$JAX_COMPILATION_CACHE_DIR`` or, unset, in
``.jax_cache/`` of the checkout (``repro.compile_cache``).
"""
import json
import sys
import time

from repro import obs
from repro.compile_cache import use_compile_cache
from repro.engine import TRANSFER
from repro.obs import install_compile_hook

MODULES = ["fig2_traversals", "fig6_latency_tradeoff", "fig7_sharding",
           "table4_runtime", "reshard_cost", "beyond_paper",
           "engine_backends", "perf_iterate", "serve_tail",
           "tenant_frontier", "routing_policies", "provisioning_policies",
           "provisioning_scale", "incremental_eval", "fault_resilience"]

# zero-arg entry point per module when it isn't ``run`` (perf_iterate's
# ``run`` is the arch-cell driver; its benchmark entry is ``run_engine``)
ENTRY = {"perf_iterate": "run_engine"}


def main() -> None:
    want = sys.argv[1:] or MODULES
    use_compile_cache()
    obs.enable()
    install_compile_hook()
    transfer_per_module = {}
    t0 = time.perf_counter()
    print("bench,metric,tags,value")
    for name in want:
        entry = ENTRY.get(name, "run")
        mod = __import__(f"benchmarks.{name}", fromlist=[entry])
        t1 = time.perf_counter()
        with TRANSFER.scope():
            out = getattr(mod, entry)()
            transfer_per_module[name] = TRANSFER.snapshot()
        if name in ENTRY and out is not None:
            # detail blob; '#'-prefixed to keep the CSV stream parseable
            for line in json.dumps(out, indent=2).splitlines():
                print(f"# {line}")
        print(f"# {name} done in {time.perf_counter()-t1:.1f}s")
    print(f"# total {time.perf_counter()-t0:.1f}s")
    with open("BENCH_metrics.json", "w") as fh:
        json.dump(
            {
                "modules": want,
                "registry": obs.REGISTRY.snapshot(),
                "transfer_per_module": transfer_per_module,
            },
            fh,
            indent=2,
        )
    print("# metrics snapshot -> BENCH_metrics.json")


if __name__ == "__main__":
    main()
