"""Bit-identity guard of the greedy driver's three entry points.

Every case runs one small seeded workload through ``replicate_workload``,
``replicate_delta`` on a fresh engine, or ``replicate_stream`` in three
chunks, and compares the result with values recorded from the greedy
driver at commit ``cdaac19``, before its UPDATE pass was shared between
the entry points and the loss-case repair: a digest of the returned scheme (and of the delta's
(object, server) pairs) plus the stats that count what the pass did.
The ``max_candidates=4`` cases send the longer paths to the exact
``update_exact`` fallback, in the main pass, in the delta pass and in the
k-resilience gate's loss-case repairs.

``f`` is integer-valued, so every cost sum is exact in float32 and
``total_cost`` compares with ``==``.
"""
import hashlib

import numpy as np
import pytest

from repro.core import ReplicationScheme
from repro.core.greedy import replicate_delta, replicate_stream, replicate_workload
from repro.core.paths import PathSet
from repro.engine import LatencyEngine
from tests.conftest import random_workload

N_OBJ, N_SRV, N_CHUNK, MAX_LEN = 60, 5, 8, 5

STATS = ("replicas", "pruned_replicas", "routed_skips", "fallback_paths",
         "resilient_violations", "failed_paths", "total_cost")


def _workload(mixed):
    """Three chunks of paths, one sharding and an integer-valued storage
    cost per object; budgets are 1, or 1 or 2 per path when ``mixed``."""
    rng = np.random.default_rng(17)
    chunks, budgets = [], []
    shard = None
    for _ in range(3):
        ps, sh = random_workload(rng, n_obj=N_OBJ, n_srv=N_SRV,
                                 n_paths=N_CHUNK, max_len=MAX_LEN)
        shard = sh if shard is None else shard
        chunks.append(ps)
        budgets.append(rng.integers(1, 3 if mixed else 2, ps.n_queries)
                       .astype(np.int32))
    f = rng.integers(1, 4, N_OBJ).astype(np.float32)
    if mixed:
        # the first chunk again, under the other budget: a path that one
        # class vectorizes reaches the other class's exact fallback, which
        # must price against the copies the first class bought
        chunks[2] = PathSet.concatenate([chunks[2], chunks[0]])
        budgets[2] = np.concatenate([budgets[2], 3 - budgets[0]])
    return chunks, budgets, shard, f


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str((a.dtype.str, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def _run(entry, policy, resilience, fused, max_candidates):
    chunks, budgets, shard, f = _workload(mixed=max_candidates == 4)
    kw = dict(f=f, policy=policy, fused=fused, max_candidates=max_candidates)
    if max_candidates == 4:
        # a capacity that lets some candidates through and not others
        kw["capacity"] = float(f.sum()) * 0.3
    out = {}
    if entry == "stream":
        scheme, stats = replicate_stream(
            list(zip(chunks, budgets)), shard, N_SRV, **kw
        )
        out["mask"] = _digest(scheme.mask)
    else:
        ps = PathSet.concatenate(chunks)
        t = np.concatenate(budgets)
        if entry == "workload":
            scheme, stats = replicate_workload(
                ps, shard, N_SRV, t, resilience=resilience, **kw
            )
            out["mask"] = _digest(scheme.mask)
        else:
            eng = LatencyEngine(ReplicationScheme.from_sharding(shard, N_SRV))
            stats, (obj, srv) = replicate_delta(
                ps, eng, t, resilience=resilience, **kw
            )
            out["mask"] = _digest(eng.scheme.mask)
            out["words"] = _digest(eng.packed.unpack())
            out["delta"] = _digest(obj, srv)
    for name in STATS:
        out[name] = getattr(stats, name)
    return out


def _cases():
    """entry x policy x resilience x fused; a loss-case repair compiles a
    program per new shape, so the separate-dispatch repairs run only in
    the two ``max_candidates=4`` cases that also cover their fallback."""
    out = []
    for entry in ("workload", "delta", "stream"):
        for policy in (None, "nearest_copy"):
            for res in ((None, 1) if entry != "stream" else (None,)):
                for fused in (False, True):
                    if res is None or fused:
                        out.append((entry, policy, res, fused, 2048))
    out += [
        ("workload", "nearest_copy", 1, False, 4),
        ("workload", None, None, True, 4),
        ("delta", None, 1, False, 4),
        ("delta", "nearest_copy", None, False, 4),
        ("stream", "nearest_copy", None, True, 4),
    ]
    return out


def _case_id(case):
    entry, policy, res, fused, mc = case
    return (f"{entry}-{policy or 'home_first'}-k{res or 0}-"
            f"{'fused' if fused else 'split'}-mc{mc}")


EXPECTED = {
    'workload-home_first-k0-split-mc2048': dict(
        mask='c95db7a4dd969a32', replicas=27, pruned_replicas=0,
        routed_skips=0, fallback_paths=0, resilient_violations=0,
        failed_paths=0, total_cost=41.0,
    ),
    'workload-home_first-k0-fused-mc2048': dict(
        mask='c95db7a4dd969a32', replicas=27, pruned_replicas=0,
        routed_skips=0, fallback_paths=0, resilient_violations=0,
        failed_paths=0, total_cost=41.0,
    ),
    'workload-home_first-k1-fused-mc2048': dict(
        mask='14fe9200cad8ea2a', replicas=55, pruned_replicas=0,
        routed_skips=0, fallback_paths=0, resilient_violations=0,
        failed_paths=0, total_cost=53.0,
    ),
    'workload-nearest_copy-k0-split-mc2048': dict(
        mask='a79ee61fc6b5a45d', replicas=19, pruned_replicas=8,
        routed_skips=0, fallback_paths=0, resilient_violations=0,
        failed_paths=0, total_cost=41.0,
    ),
    'workload-nearest_copy-k0-fused-mc2048': dict(
        mask='a79ee61fc6b5a45d', replicas=19, pruned_replicas=8,
        routed_skips=0, fallback_paths=0, resilient_violations=0,
        failed_paths=0, total_cost=41.0,
    ),
    'workload-nearest_copy-k1-fused-mc2048': dict(
        mask='145f059a52c64ea2', replicas=54, pruned_replicas=8,
        routed_skips=3, fallback_paths=0, resilient_violations=0,
        failed_paths=0, total_cost=60.0,
    ),
    'delta-home_first-k0-split-mc2048': dict(
        mask='c95db7a4dd969a32', words='c95db7a4dd969a32',
        delta='321d8640adaf4f51', replicas=27, pruned_replicas=0,
        routed_skips=0, fallback_paths=0, resilient_violations=0,
        failed_paths=0, total_cost=41.0,
    ),
    'delta-home_first-k0-fused-mc2048': dict(
        mask='c95db7a4dd969a32', words='c95db7a4dd969a32',
        delta='321d8640adaf4f51', replicas=27, pruned_replicas=0,
        routed_skips=0, fallback_paths=0, resilient_violations=0,
        failed_paths=0, total_cost=41.0,
    ),
    'delta-home_first-k1-fused-mc2048': dict(
        mask='14fe9200cad8ea2a', words='14fe9200cad8ea2a',
        delta='0e3ae91bc6fafb8d', replicas=55, pruned_replicas=0,
        routed_skips=0, fallback_paths=0, resilient_violations=0,
        failed_paths=0, total_cost=53.0,
    ),
    'delta-nearest_copy-k0-split-mc2048': dict(
        mask='c95db7a4dd969a32', words='c95db7a4dd969a32',
        delta='321d8640adaf4f51', replicas=27, pruned_replicas=0,
        routed_skips=0, fallback_paths=0, resilient_violations=0,
        failed_paths=0, total_cost=41.0,
    ),
    'delta-nearest_copy-k0-fused-mc2048': dict(
        mask='c95db7a4dd969a32', words='c95db7a4dd969a32',
        delta='321d8640adaf4f51', replicas=27, pruned_replicas=0,
        routed_skips=0, fallback_paths=0, resilient_violations=0,
        failed_paths=0, total_cost=41.0,
    ),
    'delta-nearest_copy-k1-fused-mc2048': dict(
        mask='25c1e56dd270151a', words='25c1e56dd270151a',
        delta='5ed7b492caf85bc0', replicas=55, pruned_replicas=0,
        routed_skips=4, fallback_paths=0, resilient_violations=0,
        failed_paths=0, total_cost=50.0,
    ),
    'stream-home_first-k0-split-mc2048': dict(
        mask='c95db7a4dd969a32', replicas=27, pruned_replicas=0,
        routed_skips=0, fallback_paths=0, resilient_violations=0,
        failed_paths=0, total_cost=41.0,
    ),
    'stream-home_first-k0-fused-mc2048': dict(
        mask='c95db7a4dd969a32', replicas=27, pruned_replicas=0,
        routed_skips=0, fallback_paths=0, resilient_violations=0,
        failed_paths=0, total_cost=41.0,
    ),
    'stream-nearest_copy-k0-split-mc2048': dict(
        mask='c95db7a4dd969a32', replicas=27, pruned_replicas=0,
        routed_skips=0, fallback_paths=0, resilient_violations=0,
        failed_paths=0, total_cost=41.0,
    ),
    'stream-nearest_copy-k0-fused-mc2048': dict(
        mask='c95db7a4dd969a32', replicas=27, pruned_replicas=0,
        routed_skips=0, fallback_paths=0, resilient_violations=0,
        failed_paths=0, total_cost=41.0,
    ),
    'workload-nearest_copy-k1-split-mc4': dict(
        mask='7a2fe4568c9baad1', replicas=32, pruned_replicas=1,
        routed_skips=4, fallback_paths=4, resilient_violations=3,
        failed_paths=9, total_cost=20.0,
    ),
    'workload-home_first-k0-fused-mc4': dict(
        mask='d14acaffaf832555', replicas=11, pruned_replicas=0,
        routed_skips=0, fallback_paths=2, resilient_violations=0,
        failed_paths=0, total_cost=20.0,
    ),
    'delta-home_first-k1-split-mc4': dict(
        mask='7a2fe4568c9baad1', words='7a2fe4568c9baad1',
        delta='8c744f4a123c8fd5', replicas=32, pruned_replicas=0,
        routed_skips=0, fallback_paths=5, resilient_violations=3,
        failed_paths=16, total_cost=20.0,
    ),
    'delta-nearest_copy-k0-split-mc4': dict(
        mask='d14acaffaf832555', words='d14acaffaf832555',
        delta='306b006713fe1763', replicas=11, pruned_replicas=0,
        routed_skips=2, fallback_paths=1, resilient_violations=0,
        failed_paths=0, total_cost=20.0,
    ),
    'stream-nearest_copy-k0-fused-mc4': dict(
        mask='d14acaffaf832555', replicas=11, pruned_replicas=0,
        routed_skips=1, fallback_paths=2, resilient_violations=0,
        failed_paths=0, total_cost=20.0,
    ),
}


@pytest.mark.parametrize("case", _cases(), ids=_case_id)
def test_driver_matches_recorded(case):
    assert _run(*case) == EXPECTED[_case_id(case)]
