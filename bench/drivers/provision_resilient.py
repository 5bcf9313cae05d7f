"""Closed loop of from-scratch k-resilient provisionings
(``replicate_workload(..., resilience=KResilient(k, domains))``).

As ``provision``, with the configuration's ``resilience`` (``k`` and the
fault ``domains``, null for one per server) passed to the program.  A
call fails as there, and also when the scheme it returns does not certify
the guarantee (``stats.resilient_violations > 0``).

``correct`` adds to ``provision``'s numbers ``paths_over_t_loss``: the
worst, over the loss cases, of the paths that the reference walk finds
over ``t`` on the returned scheme with the case's servers' copies gone
and the lost homes failed over by rotation (``bench.reference.resilient``).
``replica_excess`` is taken against the plain k-resilient sequential
greedy; the control is the plain greedy without resilience, judged under
the loss cases.
"""
from __future__ import annotations

import time

import numpy as np

from bench.drivers.provision import SPAN, end_to_end  # noqa: F401
from bench.drivers.provision import summary as _summary
from bench.gen import traffic as gen
from bench.reference import greedy as plain
from bench.reference import resilient as ref
from bench.reference.walk import walk_latencies


def _resilience(cell):
    r = cell.config["resilience"]
    doms = r.get("domains")
    return int(r["k"]), (None if doms is None
                         else tuple(tuple(int(s) for s in d) for d in doms))


def _cases(cell):
    k, domains = _resilience(cell)
    return ref.loss_cases(cell.data["n_servers"], k, domains)


def _call(cell, ps):
    from repro.core.greedy import replicate_workload
    from repro.engine.resilience import KResilient

    k, domains = _resilience(cell)
    return replicate_workload(ps, cell.data["shard"], cell.data["n_servers"],
                              cell.t, policy=cell.traffic["policy"],
                              resilience=KResilient(k, domains))


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
            rec["failed"] = bool(stats.failed_paths or stats.routed_violations
                                 or stats.resilient_violations)
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


def summary(cell, state, recs) -> dict:
    """``provision``'s summary, the resilience phase's readings, and the
    shapes of the masked re-walk (for ``resilient_walk_roofline``)."""
    out = _summary(cell, state, recs)
    st = [r["stats"] for r in recs if "stats" in r]
    S = cell.data["n_servers"]
    out["resilience"] = {
        "rounds": sum(s.resilience_rounds for s in st),
        "violations": sum(s.resilient_violations for s in st),
    }
    out["resilient_walk"] = {
        "D": len(_cases(cell)),
        "P": out["paths_processed"] / max(len(st), 1),
        "L": cell.max_len, "W": -(-S // 32), "n": cell.data["n_objects"],
    }
    return out


def scheme_numbers(cell, ps_arrays, mask, ref_replicas) -> dict:
    """The compared numbers of one returned scheme."""
    o, ln, _ = ps_arrays
    shard = cell.data["shard"]
    pol = cell.traffic["policy"]
    lat = walk_latencies(o, ln, mask, shard, pol)
    n = len(shard)
    held = int(mask[np.arange(n), shard].sum())
    return {
        "paths_over_t": int((lat > cell.t).sum()),
        "paths_over_t_loss": ref.over_t_under_loss(
            o, ln, mask, shard, cell.t, pol, _cases(cell)),
        "originals_lost": n - held,
        "replica_excess": (int(mask.sum()) - held) / max(ref_replicas, 1)
        - 1.0,
    }


def reference_replicas(cell, ps_arrays, t: int):
    """The plain k-resilient greedy's scheme and its replica count."""
    o, ln, _ = ps_arrays
    k, domains = _resilience(cell)
    m = ref.provision(o, ln, cell.data["shard"], cell.data["n_servers"], t,
                      cell.traffic["policy"], k=k, domains=domains)
    return m, int(m.sum()) - len(cell.data["shard"])


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
    """The contract's control: the plain greedy provisioned without
    resilience in the program's place, judged under the loss cases."""
    worst: dict = {}
    for arrays in state["calls"]:
        o, ln, _ = arrays
        _, n_ref = reference_replicas(cell, arrays, cell.t)
        m = plain.provision(o, ln, cell.data["shard"], cell.data["n_servers"],
                            cell.t, cell.traffic["policy"])
        for name, v in scheme_numbers(cell, arrays, m, n_ref).items():
            worst[name] = max(worst.get(name, v), v)
    return worst
