"""Closed loop of from-scratch provisionings (``replicate_workload``).

Set-up draws the run's distinct calls from the seed (fixed path count and
length, see ``bench.gen.traffic``) and runs each once, which compiles
every program their shapes need.  The window then runs them back to back,
in turn, until ``seconds`` have passed; each call ends with its scheme on
the host.  Only the deployment's semantics are passed (servers, sharding,
budget ``t``, routing policy); every implementation option is the
program's default.

``correct``: for every call of the window, the reference walk of every
path under the call's policy on the returned scheme (no path over ``t``,
no original dropped), and the replicas it holds against the plain
sequential greedy on the same paths.
"""
from __future__ import annotations

import time

import numpy as np

from bench.gen import traffic as gen
from bench.reference import greedy as ref
from bench.reference.walk import walk_latencies

SPAN = "bench.provision"


def _call(cell, ps):
    from repro.core.greedy import replicate_workload

    return replicate_workload(ps, cell.data["shard"], cell.data["n_servers"],
                              cell.t, policy=cell.traffic["policy"])


def setup(cell):
    from repro.core.paths import PathSet

    calls = gen.provision_calls(cell.data, cell.traffic, cell.max_len,
                                cell.seed)
    pathsets = [PathSet(o, ln, q) for o, ln, q in calls]
    for ps in pathsets:
        _call(cell, ps)
    return {"pathsets": pathsets, "calls": calls}


def _timed(cell, ps, k: int, span) -> dict:
    """One timed call, from call to the scheme on the host."""
    rec = {"sample": k, "paths": ps.n_paths}
    with span(SPAN):
        t0 = time.perf_counter()
        try:
            scheme, stats = _call(cell, ps)
            rec["dt"] = time.perf_counter() - t0
            rec["mask"] = scheme.mask
            rec["stats"] = stats
            rec["failed"] = bool(stats.failed_paths or stats.routed_violations)
        except Exception as e:  # a call that raises is a failed call
            rec["dt"] = time.perf_counter() - t0
            rec["error"] = repr(e)
            rec["failed"] = True
    return rec


def window(cell, state, seconds: float, span) -> list:
    recs = []
    pathsets = state["pathsets"]
    t_end = time.perf_counter() + seconds
    first: dict = {}
    while time.perf_counter() < t_end:
        k = len(recs) % len(pathsets)
        rec = _timed(cell, pathsets[k], k, span)
        # a call that repeats an earlier call's scheme keeps one copy
        if "mask" in rec:
            m = first.setdefault(k, rec["mask"])
            if m is not rec["mask"] and np.array_equal(m, rec["mask"]):
                rec["mask"] = m
        recs.append(rec)
    return recs


def once(cell, state, span) -> list:
    """Each distinct call once."""
    return [_timed(cell, ps, k, span) for k, ps in enumerate(state["pathsets"])]


def end_to_end(cell, recs) -> dict:
    done = [r for r in recs if "error" not in r]
    return {"provision_paths_per_s":
            sum(r["paths"] for r in done) / sum(r["dt"] for r in done)}


def summary(cell, state, recs) -> dict:
    """What the per-layer readers need of the window, and an earlier
    output line."""
    import inspect
    import math

    from repro.core.greedy import replicate_workload

    st = [r["stats"] for r in recs if "stats" in r]
    shard = cell.data["shard"]
    H = max(max_subpaths(o, ln, shard) for o, ln, _ in state["calls"])
    t = cell.t
    S = cell.data["n_servers"]
    return {
        "calls": len(recs),
        "paths_processed": sum(s.paths_processed for s in st),
        "replicas": sum(s.replicas for s in st),
        "pruned_replicas": sum(s.pruned_replicas for s in st),
        "fallback_paths": sum(s.fallback_paths for s in st),
        "routed_skips": sum(s.routed_skips for s in st),
        "call_s": [round(r["dt"], 4) for r in recs],
        "update": {
            "B": inspect.signature(replicate_workload)
            .parameters["batch_size"].default,
            "L": cell.max_len, "W": -(-S // 32), "S": S,
            "C": math.comb(max(H, t, 1), t), "Hp1": max(H, t, 1) + 1,
            "gate": cell.traffic["policy"] != "home_first",
            "additions": sum(s.replicas + s.pruned_replicas for s in st),
        },
    }


def max_subpaths(objects, lengths, shard) -> int:
    """Largest number of server changes along a path (h under d)."""
    srv = shard[np.maximum(objects, 0)]
    live = np.arange(objects.shape[1])[None, 1:] < lengths[:, None]
    return int(((srv[:, 1:] != srv[:, :-1]) & live).sum(axis=1).max())


def scheme_numbers(cell, ps_arrays, mask, ref_replicas) -> dict:
    """The compared numbers of one returned scheme."""
    o, ln, _ = ps_arrays
    shard = cell.data["shard"]
    lat = walk_latencies(o, ln, mask, shard, cell.traffic["policy"])
    n = len(shard)
    replicas = int(mask.sum()) - int(mask[np.arange(n), shard].sum())
    return {
        "paths_over_t": int((lat > cell.t).sum()),
        "originals_lost": int(n - mask[np.arange(n), shard].sum()),
        "replica_excess": replicas / max(ref_replicas, 1) - 1.0,
    }


def reference_replicas(cell, ps_arrays, t: int):
    o, ln, _ = ps_arrays
    m = ref.provision(o, ln, cell.data["shard"], cell.data["n_servers"], t,
                      cell.traffic["policy"])
    n = len(cell.data["shard"])
    return m, int(m.sum()) - n


def check(cell, state, recs) -> dict:
    """Worst reading over the window's calls of each compared number."""
    calls = state["calls"]
    refs = {}
    seen = set()
    worst: dict = {}
    for r in recs:
        if "mask" not in r or id(r["mask"]) in seen:
            continue
        seen.add(id(r["mask"]))
        k = r["sample"]
        if k not in refs:
            refs[k] = reference_replicas(cell, calls[k], cell.t)[1]
        for name, v in scheme_numbers(cell, calls[k], r["mask"],
                                      refs[k]).items():
            worst[name] = max(worst.get(name, v), v)
    return worst


def control(cell, state) -> dict:
    """The contract's control: the plain reference in the program's place,
    provisioned for the budget ``t + 1``, judged against ``t``."""
    worst: dict = {}
    for arrays in state["calls"]:
        _, n_ref = reference_replicas(cell, arrays, cell.t)
        m, _ = reference_replicas(cell, arrays, cell.t + 1)
        for name, v in scheme_numbers(cell, arrays, m, n_ref).items():
            worst[name] = max(worst.get(name, v), v)
    return worst
