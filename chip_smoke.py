"""Drive the replication provisioner once on a TPU, at LDBC SNB SF1 size.

    python chip_smoke.py              # one chip: provisioning, feasibility, repair
    python chip_smoke.py --chips 4    # only the path-sharded provisioning path

Data: ``snb_like(scale=70)`` — 3,206,000 objects, about the 3.18 M vertices
of LDBC SNB SF1 — hash-sharded over 6 servers, and 20,000 SNB short-read
queries materialized into causal access paths (t = 1 hop budget).

One chip runs, through the normal entry points:

  provision    ``replicate_workload`` with its defaults, and with
               ``policy="nearest_copy", fused=True`` on the Pallas and the
               jnp backend — the two schemes must be bit-identical;
  feasibility  ``LatencyEngine`` path latencies on both backends under
               ``home_first`` and ``nearest_copy`` must be equal, and equal
               to the reference oracle on a seeded 2,000-path sample;
               ``is_feasible`` must hold, incrementally too;
  repair       one ``snb_drift`` phase repaired by ``replicate_delta``
               (``nearest_copy``, fused) on both backends — identical deltas.

``--chips 4`` runs only ``replicate_workload(..., mesh=provisioning_mesh(4))``
on both backends and the Pallas call on one device, over the same graph
with 5,000 queries whose budgets alternate t = 1, 2 (two budget classes, so
the mesh loop runs twice and the second starts from the first's state):
the schemes must be equal.

Each phase prints its wall time up to a host readback: smoke times, not
benchmark results.  Any failed phase or comparison exits non-zero.  The
last line of a good run is one JSON object naming the device.  Without a
TPU the script exits 1 and prints no result.

The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when that is set, and
``.jax_cache/`` in this checkout otherwise.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SCALE = 70           # snb_like scale: 3,206,000 objects (LDBC SNB SF1 ~3.18 M)
N_SERVERS = 6
N_QUERIES = 20_000
N_ORACLE = 2_000     # seeded sample checked against the reference oracle
DRIFT_QUERIES = 2_000
# the four-chip path costs four chips per second: the full 3.2 M-object
# graph, a quarter of the queries (46,436 paths), per-query budgets
# alternating over T_4 (two budget classes)
N_QUERIES_4 = 5_000
T = 1
T_4 = (1, 2)


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"  {what}: {'ok' if ok else 'FAILED'}", flush=True)
    if not ok:
        raise SmokeFailure(what)


class phase:
    """Prints a phase's wall time; the body ends in host arrays."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        print(f"[{self.name}]", flush=True)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            dt = time.perf_counter() - self.t0
            print(f"  wall {dt:.3f} s", flush=True)


def build_data(scale: int, n_queries: int, seed: int = 0):
    from repro.graph import make_sharding, snb_like
    from repro.workload import snb_workload_materialized

    with phase("data"):
        snb = snb_like(scale, seed=seed)
        shard = make_sharding("hash", snb.graph, N_SERVERS, seed=seed)
        ps = snb_workload_materialized(snb, n_queries=n_queries, seed=seed)
        print(f"  objects {snb.graph.n_nodes}  edges {snb.graph.n_edges}  "
              f"servers {N_SERVERS}  queries {n_queries}  paths {ps.n_paths}  "
              f"max_len {ps.max_len}", flush=True)
    return snb, shard, ps


def provision(ps, shard, label: str, t=T, **kw):
    from repro.core.greedy import replicate_workload

    with phase(f"provision {label}"):
        scheme, stats = replicate_workload(ps, shard, N_SERVERS, t, **kw)
        print(f"  replicas {scheme.replica_count()}  failed "
              f"{stats.failed_paths}  fallback {stats.fallback_paths}  "
              f"routed_skips {stats.routed_skips}  routed_violations "
              f"{stats.routed_violations}", flush=True)
    return scheme


def same_scheme(a, b) -> bool:
    import numpy as np

    return bool(np.array_equal(a.mask, b.mask))


def feasibility(scheme, ps, shard, label: str, n_oracle: int,
                feasible_under: tuple[str, ...]) -> None:
    """Both backends and the oracle agree under both policies; the scheme
    meets t under the policies of ``feasible_under``."""
    import numpy as np

    from repro.core.reference import (
        path_latencies_reference,
        routed_path_latencies_reference,
    )
    from repro.engine import LatencyEngine

    rng = np.random.default_rng(0)
    idx = np.sort(rng.choice(ps.n_paths, min(n_oracle, ps.n_paths),
                             replace=False))
    sample = ps.select(idx)
    for policy in ("home_first", "nearest_copy"):
        with phase(f"feasibility {label} {policy}"):
            lat = {
                b: LatencyEngine(scheme, backend=b).path_latencies(
                    ps, policy=policy)
                for b in ("pallas", "jnp")
            }
            check(np.array_equal(lat["pallas"], lat["jnp"]),
                  f"pallas == jnp path latencies ({ps.n_paths} paths)")
            if policy == "home_first":
                ref = path_latencies_reference(
                    sample.objects, sample.lengths, scheme.mask, shard)
            else:
                ref = routed_path_latencies_reference(
                    sample.objects, sample.lengths, scheme.mask, shard,
                    policy=policy)
            check(np.array_equal(lat["pallas"][idx], ref),
                  f"== reference oracle ({len(idx)} sampled paths)")
            if policy in feasible_under:
                eng = LatencyEngine(scheme, backend="pallas")
                check(eng.is_feasible(ps, T, policy=policy),
                      f"is_feasible t={T}")
                check(eng.is_feasible(ps, T, policy=policy, incremental=True),
                      "is_feasible incremental")
            print(f"  max latency {int(lat['pallas'].max())}", flush=True)


def repair(snb, scheme, drift_queries: int) -> None:
    import numpy as np

    from repro.core.greedy import replicate_delta
    from repro.engine import LatencyEngine
    from repro.serve.drift import drift_stream, snb_drift

    phases = snb_drift(snb, n_phases=2, queries_per_phase=drift_queries,
                       seed=0)
    added = list(drift_stream(phases))[1].added
    deltas = {}
    for backend in ("pallas", "jnp"):
        with phase(f"repair nearest_copy {backend}"):
            eng = LatencyEngine(scheme.copy(), backend=backend)
            stats, (obj, srv) = replicate_delta(
                added, eng, T, policy="nearest_copy", fused=True,
                policy_backend=backend,
            )
            deltas[backend] = (np.asarray(obj), np.asarray(srv))
            print(f"  delta paths {added.n_paths}  additions {len(obj)}  "
                  f"routed_violations {stats.routed_violations}", flush=True)
    check(all(np.array_equal(p, j) for p, j in
              zip(deltas["pallas"], deltas["jnp"])),
          "pallas == jnp repair delta")


def run_one_chip(scale=SCALE, n_queries=N_QUERIES, n_oracle=N_ORACLE,
                 drift_queries=DRIFT_QUERIES) -> None:
    snb, shard, ps = build_data(scale, n_queries)
    s_default = provision(ps, shard, "default (home_first, jnp)")
    nc = dict(policy="nearest_copy", fused=True)
    s_pallas = provision(ps, shard, "nearest_copy fused pallas",
                         policy_backend="pallas", **nc)
    s_jnp = provision(ps, shard, "nearest_copy fused jnp",
                      policy_backend="jnp", **nc)
    check(same_scheme(s_pallas, s_jnp), "pallas == jnp scheme (nearest_copy)")
    # a home_first-feasible scheme is nearest_copy-feasible too, not the
    # other way round
    feasibility(s_default, ps, shard, "default scheme", n_oracle,
                feasible_under=("home_first", "nearest_copy"))
    feasibility(s_pallas, ps, shard, "nearest_copy scheme", n_oracle,
                feasible_under=("nearest_copy",))
    repair(snb, s_pallas, drift_queries)


def run_four_chips(scale=SCALE, n_queries=N_QUERIES_4) -> None:
    """The one-chip run already pins pallas == jnp on one device; here both
    backends on the 4-chip mesh must give the one-device Pallas scheme."""
    import numpy as np

    from repro.engine.sharding import provisioning_mesh

    _, shard, ps = build_data(scale, n_queries)
    mesh = provisioning_mesh(4)
    t = np.resize(np.asarray(T_4, np.int32), ps.n_queries)
    print(f"  budgets t={T_4} alternating over {ps.n_queries} queries",
          flush=True)
    nc = dict(t=t, policy="nearest_copy", fused=True)
    base = provision(ps, shard, "nearest_copy fused pallas one device",
                     policy_backend="pallas", **nc)
    for backend in ("pallas", "jnp"):
        s = provision(ps, shard, f"nearest_copy fused {backend} mesh=4",
                      policy_backend=backend, mesh=mesh, **nc)
        check(same_scheme(s, base), f"{backend} mesh=4 == one-device scheme")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from repro.compile_cache import use_compile_cache

    print(f"compile cache {use_compile_cache()}", flush=True)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (platform {devices[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} devices",
              file=sys.stderr)
        return 1
    print(f"device {devices[0].device_kind} x{len(devices)}  jax "
          f"{jax.__version__}", flush=True)
    try:
        if args.chips == 4:
            run_four_chips()
        else:
            run_one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
