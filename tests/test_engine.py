"""Three-way backend parity for the unified LatencyEngine.

reference (pure python) vs jnp (packed lax.scan) vs pallas (TPU kernel,
interpret mode on CPU) must agree EXACTLY — integer traversal counts —
over randomized shards/schemes/path lengths, including the documented
edge cases: empty pathsets, length-1 (single-object) paths, and fully
replicated schemes.
"""
import numpy as np
import pytest

from repro.core import PathSet, ReplicationScheme
from repro.engine import LatencyEngine, PackedScheme, pack_bool_mask, unpack_words

BACKENDS = ("reference", "jnp", "pallas")


def _random_case(rng, n_obj, n_srv, n_paths, max_len, extra=0.1):
    shard = rng.integers(0, n_srv, n_obj).astype(np.int32)
    scheme = ReplicationScheme.from_sharding(shard, n_srv)
    k = int(extra * n_obj * n_srv)
    if k:
        scheme.mask[rng.integers(0, n_obj, k), rng.integers(0, n_srv, k)] = True
    paths = [
        rng.integers(0, n_obj, rng.integers(1, max_len + 1)).tolist()
        for _ in range(n_paths)
    ]
    return PathSet.from_lists(paths), scheme


@pytest.mark.parametrize("n_srv", [2, 5, 33, 70])
@pytest.mark.parametrize("n_paths,max_len", [(1, 1), (37, 4), (300, 9)])
def test_three_way_parity(rng, n_srv, n_paths, max_len):
    ps, scheme = _random_case(rng, 150, n_srv, n_paths, max_len)
    outs = {
        b: LatencyEngine(scheme, backend=b, chunk=128).path_latencies(ps)
        for b in BACKENDS
    }
    assert np.array_equal(outs["reference"], outs["jnp"]), n_srv
    assert np.array_equal(outs["reference"], outs["pallas"]), n_srv
    assert outs["jnp"].dtype == np.int32


def test_parity_empty_pathset(rng):
    ps = PathSet.from_lists([])
    _, scheme = _random_case(rng, 20, 3, 1, 2)
    for b in BACKENDS:
        out = LatencyEngine(scheme, backend=b).path_latencies(ps)
        assert out.shape == (0,)


def test_parity_single_object_paths(rng):
    # a one-object path never traverses (h = 0) under every backend
    ps = PathSet.from_lists([[i] for i in range(10)])
    _, scheme = _random_case(rng, 10, 4, 1, 1)
    for b in BACKENDS:
        assert LatencyEngine(scheme, backend=b).path_latencies(ps).sum() == 0


def test_parity_fully_replicated(rng):
    # full replication: everything local after the root -> h = 0 everywhere
    ps, scheme = _random_case(rng, 60, 7, 100, 6)
    scheme.mask[:] = True
    for b in BACKENDS:
        assert LatencyEngine(scheme, backend=b).path_latencies(ps).sum() == 0


def test_parity_under_incremental_updates(rng):
    """Device scatter-OR additions keep all backends in agreement."""
    ps, scheme = _random_case(rng, 80, 5, 120, 6, extra=0.0)
    eng = {b: LatencyEngine(scheme, backend=b) for b in BACKENDS}
    for _ in range(3):
        objs = rng.integers(0, 80, 40)
        srvs = rng.integers(0, 5, 40)
        for e in eng.values():
            e.add_replicas(objs, srvs)
        outs = {b: e.path_latencies(ps) for b, e in eng.items()}
        assert np.array_equal(outs["reference"], outs["jnp"])
        assert np.array_equal(outs["reference"], outs["pallas"])


def test_packed_roundtrip_and_scatter(rng):
    mask = rng.random((50, 40)) < 0.3
    shard = rng.integers(0, 40, 50).astype(np.int32)
    mask[np.arange(50), shard] = True
    packed = PackedScheme.from_mask(mask, shard)
    assert np.array_equal(packed.unpack(), mask)
    assert packed.replica_count() == int(mask.sum()) - 50
    # duplicate pairs + pairs crossing word boundaries
    objs = np.array([0, 0, 0, 3, 3, -1], np.int32)
    srvs = np.array([31, 32, 31, 39, 0, 5], np.int32)
    packed.add(objs, srvs)
    want = mask.copy()
    want[0, 31] = want[0, 32] = want[3, 39] = want[3, 0] = True
    assert np.array_equal(packed.unpack(), want)


def _pack_oracle(mask):
    """The 32-lane formula: each row widened to 32 uint32 lanes a word."""
    R, S = mask.shape
    W = (S + 31) // 32
    padded = np.zeros((R, W * 32), dtype=bool)
    padded[:, :S] = mask
    bits = padded.reshape(R, W, 32).astype(np.uint32)
    weights = (np.uint32(1) << np.arange(32, dtype=np.uint32))[None, None, :]
    return (bits * weights).sum(axis=2).astype(np.uint32)


def _unpack_oracle(words, n_servers):
    R, W = words.shape
    shifts = np.arange(32, dtype=np.uint32)
    bits = (words[:, :, None] >> shifts[None, None, :]) & np.uint32(1)
    return bits.reshape(R, W * 32)[:, :n_servers].astype(bool)


@pytest.mark.parametrize("rows", [0, 1, 1000])
@pytest.mark.parametrize("n_srv", [1, 6, 8, 9, 31, 32, 33, 64, 70])
def test_pack_unpack_inverse(rng, n_srv, rows):
    mask = rng.random((rows, n_srv)) < 0.5
    wide = rng.random((rows, 2 * n_srv + 1)) < 0.5
    wide[:, 1::2] = mask  # a non-contiguous column slice that equals mask
    for given in (mask, wide[:, 1::2], mask.astype(np.uint8)):
        words = pack_bool_mask(given)
        assert words.shape == (rows, (n_srv + 31) // 32)
        assert words.dtype == np.uint32
        assert np.array_equal(words, _pack_oracle(mask))
        for s in range(n_srv):  # bit s % 32 of word s // 32, and only there
            bit = (words[:, s // 32] >> np.uint32(s % 32)) & np.uint32(1)
            assert np.array_equal(bit.astype(bool), mask[:, s]), s
        back = unpack_words(words, n_srv)
        assert np.array_equal(back, mask)
        assert np.array_equal(back, _unpack_oracle(words, n_srv))
        assert back.dtype == bool
        assert back.flags.c_contiguous and back.flags.writeable
        back[:, :] = True  # owned: writing leaves the words alone
        assert np.array_equal(words, _pack_oracle(mask))


def test_unpack_drops_the_sacrificial_row_on_the_host(rng):
    from repro import obs

    mask = rng.random((50, 6)) < 0.3
    packed = PackedScheme.from_mask(mask, np.zeros(50, np.int32))
    # a negative object lands in the sacrificial row and sets its bit
    packed.add([-1, 7], [4, 2])
    assert np.asarray(packed.words)[-1].any()
    mask[7, 2] = True
    was = obs.enabled()
    obs.REGISTRY.reset()
    obs.enable()
    try:
        got = packed.unpack()
        snap = obs.REGISTRY.snapshot()
    finally:
        (obs.enable if was else obs.disable)()
        obs.REGISTRY.reset()
    assert got.shape == (50, 6)
    assert np.array_equal(got, mask)
    assert snap["repro.engine.d2h_calls"] == 1
    assert snap["repro.engine.codec.n"] == 1


def test_margin_costs_against_snapshot(rng):
    ps, scheme = _random_case(rng, 40, 6, 10, 4)
    eng = LatencyEngine(scheme)
    f = rng.random(40).astype(np.float32)
    objs = rng.integers(0, 40, (8, 5)).astype(np.int32)
    srvs = rng.integers(0, 6, (8, 5)).astype(np.int32)
    objs[2, 3] = -1  # ignored pair
    got = eng.margin_costs(objs, srvs, f)
    want = np.zeros(8, np.float32)
    for i in range(8):
        for j in range(5):
            v, s = int(objs[i, j]), int(srvs[i, j])
            if v >= 0 and not scheme.mask[v, s]:
                want[i] += f[v]
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_is_feasible_uses_precomputed(rng):
    ps, scheme = _random_case(rng, 60, 4, 50, 5)
    eng = LatencyEngine(scheme)
    pl = eng.path_latencies(ps)
    t = int(pl.max())
    assert eng.is_feasible(ps, t, path_lats=pl)
    assert not eng.is_feasible(ps, t - 1, path_lats=pl)
    # module-level convenience accepts the precomputed array too
    from repro.core import is_latency_feasible

    assert is_latency_feasible(ps, scheme, t, path_lats=pl)


def test_engine_refresh_after_host_mutation(rng):
    ps, scheme = _random_case(rng, 40, 4, 30, 5, extra=0.0)
    eng = LatencyEngine(scheme)
    before = eng.path_latencies(ps)
    scheme.mask[:, :] = True  # direct host mutation bypasses the engine
    eng.refresh()
    assert eng.path_latencies(ps).sum() == 0
    assert before.sum() >= 0
