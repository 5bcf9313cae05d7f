"""The plain reference against the program's own oracle
(``repro.core.reference``) at a tiny size, under both policies."""
import numpy as np
import pytest

from bench.reference import greedy as rg
from bench.reference.walk import walk_latencies

POLICIES = ["home_first", "nearest_copy"]


def _random(seed, n=150, S=5, P=300, L=6):
    rng = np.random.default_rng(seed)
    shard = rng.integers(0, S, n).astype(np.int32)
    lengths = rng.integers(1, L + 1, P).astype(np.int32)
    objects = np.full((P, L), -1, np.int32)
    for i in range(P):
        objects[i, : lengths[i]] = rng.integers(0, n, lengths[i])
    return shard, objects, lengths


@pytest.mark.parametrize("policy", POLICIES)
def test_walk_matches_oracle(policy):
    from repro.core.reference import routed_path_latencies_reference

    shard, objects, lengths = _random(1)
    rng = np.random.default_rng(2)
    mask = rng.random((len(shard), 5)) < 0.25
    mask[np.arange(len(shard)), shard] = True
    assert np.array_equal(
        walk_latencies(objects, lengths, mask, shard, policy),
        routed_path_latencies_reference(objects, lengths, mask, shard,
                                        policy=policy))


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("t", [1, 2])
def test_sequential_greedy_matches_oracle(policy, t):
    from repro.core.paths import PathSet
    from repro.core.reference import replicate_workload_exact

    shard, objects, lengths = _random(3)
    ps = PathSet(objects, lengths, np.arange(len(objects), dtype=np.int32))
    scheme, stats = replicate_workload_exact(ps, shard, 5, t, policy=policy,
                                             prune=False)
    mask = rg.provision(objects, lengths, shard, 5, t, policy,
                        do_prune=False)
    assert np.array_equal(mask, scheme.mask)
    assert not len(rg.over_budget(mask, shard, objects, lengths,
                                  np.full(len(lengths), t), policy))


def test_prune_keeps_feasibility_and_drops_replicas():
    shard, objects, lengths = _random(4)
    t = np.full(len(lengths), 1)
    full = rg.provision(objects, lengths, shard, 5, 1, "nearest_copy",
                        do_prune=False)
    pruned = full.copy()
    n = rg.prune(pruned, shard, objects, lengths, t, "nearest_copy")
    assert n > 0 and pruned.sum() == full.sum() - n
    assert not (pruned & ~full).any()
    assert not len(rg.over_budget(pruned, shard, objects, lengths, t,
                                  "nearest_copy"))
