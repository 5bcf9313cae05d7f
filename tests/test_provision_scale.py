"""Production-scale provisioning suite (PR 6).

Pins the contracts of the fused-megakernel pipeline:

  * **fused parity** — the single-dispatch fused UPDATE step (gate +
    candidate scoring + bit-test + scatter-OR + on-device stats) produces
    the same scheme as the PR-5 separate-dispatch pipeline, bit-identically,
    for every routing policy and for both device backends (jnp | pallas);
    total cost matches to float tolerance (f32 accumulation order differs);
  * **fused prune parity** — the batched independent-group prune makes
    exactly the serial per-candidate decisions;
  * **transfer accounting** — alignment-pad bytes ride ``padded_bytes``,
    never ``h2d_bytes`` (payload stays exact);
  * **streaming** — ``replicate_stream`` over a chunked ``PathStream``
    equals the same chunks through warm-started ``replicate_delta``, with
    peak host residency = one chunk, and streams are single-use;
  * **load-aware provisioning** — a skewed load forecast shifts where the
    queue-aware greedy buys replicas (off the hot server), identically
    fused and separate;
  * **sharding** — the mesh-sharded driver equals the single-device driver
    (skips cleanly with one device; a slow subprocess variant forces 4
    host devices via XLA_FLAGS);
  * **wall-clock guard** — the benchmark's default grid point stays under
    its stated budget (tier-1: catches dispatch-count regressions that
    parity tests cannot see).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.provisioning_scale import DEFAULT_BUDGET_S, default_grid_point
from repro.core.greedy import (
    replicate_delta,
    replicate_stream,
    replicate_workload,
)
from repro.core.paths import PathSet
from repro.core.replication import ReplicationScheme, prune_scheme_replicas
from repro.engine import LatencyEngine, PathStream, TRANSFER, to_device
from repro.engine.sharding import device_count, provisioning_mesh
from tests.conftest import random_workload

POLICIES = [None, "nearest_copy", "queue_aware", "nearest_copy_dp"]


def _case(rng, n_paths=110):
    n_srv = 5
    ps, shard = random_workload(
        rng, n_obj=90, n_srv=n_srv, n_paths=n_paths, max_len=6
    )
    f = rng.uniform(0.5, 2.0, 90).astype(np.float32)
    return ps, shard, n_srv, f


# ---------------------------------------------------------------------------
# fused parity: megakernel pipeline == separate-dispatch pipeline
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", POLICIES)
def test_fused_parity_all_backends(rng, policy):
    ps, shard, n_srv, f = _case(rng)
    sep, sstats = replicate_workload(
        ps, shard, n_srv, t=2, f=f, policy=policy, fused=False
    )
    for backend in ("jnp", "pallas"):
        fus, fstats = replicate_workload(
            ps, shard, n_srv, t=2, f=f, policy=policy,
            policy_backend=backend, fused=True,
        )
        assert np.array_equal(sep.mask, fus.mask), (policy, backend)
        assert np.isclose(sstats.total_cost, fstats.total_cost, rtol=1e-5)
        assert sstats.failed_paths == fstats.failed_paths
        assert sstats.routed_skips == fstats.routed_skips


def test_fused_parity_vector_budgets_and_capacity(rng):
    ps, shard, n_srv, f = _case(rng)
    t_vec = rng.integers(1, 4, ps.n_queries).astype(np.int32)
    for kw in ({"t": t_vec}, {"t": 2, "capacity": 60.0}):
        sep, ss = replicate_workload(
            ps, shard, n_srv, f=f, policy="nearest_copy", fused=False, **kw
        )
        fus, fs = replicate_workload(
            ps, shard, n_srv, f=f, policy="nearest_copy", fused=True, **kw
        )
        assert np.array_equal(sep.mask, fus.mask)
        assert ss.failed_paths == fs.failed_paths


def test_fused_reference_backend_downgrades(rng):
    """fused needs a device backend; reference silently runs separate."""
    ps, shard, n_srv, f = _case(rng, n_paths=40)
    ref, _ = replicate_workload(
        ps, shard, n_srv, t=2, f=f, policy="nearest_copy",
        policy_backend="reference", fused=True,
    )
    sep, _ = replicate_workload(
        ps, shard, n_srv, t=2, f=f, policy="nearest_copy", fused=False
    )
    assert np.array_equal(ref.mask, sep.mask)


# ---------------------------------------------------------------------------
# fused prune: batched independent groups == serial candidate sweep
# ---------------------------------------------------------------------------
def test_fused_prune_decision_identical(rng):
    ps, shard, n_srv, f = _case(rng)
    scheme, _ = replicate_workload(
        ps, shard, n_srv, t=1, f=f, policy="nearest_copy",
        policy_prune=False, fused=True,
    )
    serial = ReplicationScheme(scheme.mask.copy(), shard)
    batched = ReplicationScheme(scheme.mask.copy(), shard)
    n_s, b_s = (
        prune_scheme_replicas(s, ps, 1, policy="nearest_copy", f=f, fused=fu)
        for s, fu in ((serial, False), (batched, True))
    )
    assert np.array_equal(serial.mask, batched.mask)
    assert n_s == b_s  # (dropped, bytes_saved) identical, not just masks


# ---------------------------------------------------------------------------
# transfer accounting: pad bytes are not payload
# ---------------------------------------------------------------------------
def test_transfer_pad_bytes_separate():
    payload = np.zeros((100, 4), np.int32)
    padded = np.zeros((128, 4), np.int32)
    to_device(payload)
    assert TRANSFER.h2d_bytes == payload.nbytes
    assert TRANSFER.padded_bytes == 0
    to_device(padded, payload_bytes=payload.nbytes)
    assert TRANSFER.h2d_bytes == 2 * payload.nbytes
    assert TRANSFER.padded_bytes == padded.nbytes - payload.nbytes
    snap = TRANSFER.snapshot()
    assert snap["padded_bytes"] == 28 * 4 * 4


def test_greedy_batch_pad_rows_not_payload(rng):
    """The driver pads batches to a fixed jit shape; those rows must land
    in padded_bytes, leaving h2d payload == the actual workload bytes."""
    ps, shard, n_srv, f = _case(rng, n_paths=70)  # 70 < batch_size=256
    TRANSFER.reset()
    replicate_workload(ps, shard, n_srv, t=2, f=f, fused=True)
    assert TRANSFER.padded_bytes > 0
    assert TRANSFER.h2d_bytes > 0


# ---------------------------------------------------------------------------
# streaming
# ---------------------------------------------------------------------------
def test_stream_equals_chunked_deltas(rng):
    ps, shard, n_srv, f = _case(rng, n_paths=150)
    chunk = 50
    chunks = [ps.select(np.arange(i, min(i + chunk, ps.n_paths)))
              for i in range(0, ps.n_paths, chunk)]

    scheme_d = ReplicationScheme.from_sharding(shard, n_srv)
    eng = LatencyEngine(scheme_d)
    for c in chunks:
        replicate_delta(c, eng, 2, f=f, policy="nearest_copy", fused=True)

    stream = PathStream(iter(chunks))
    scheme_s, stats = replicate_stream(
        stream, shard, n_srv, t=2, f=f, policy="nearest_copy", fused=True
    )
    assert np.array_equal(scheme_d.mask, scheme_s.mask)
    # per-chunk redundancy pruning dedups before UPDATE; the stream-level
    # counter sees every ingested path
    assert stats.paths_processed <= ps.n_paths
    assert stream.stats.total_paths == ps.n_paths
    assert stats.peak_resident_paths == chunk
    assert stats.peak_resident_paths < ps.n_paths
    assert stream.stats.chunks == len(chunks)


def test_stream_tables_bounded_residency(rng, monkeypatch):
    """Deep-path candidate tables stream in bounded chunks, identically.

    Forcing the stream threshold down makes every budget class take the
    device-assembled construction; the resulting scheme must be
    bit-identical to the host-stacked build, and the StreamStats must
    show peak table residency pinned at the chunk size — strictly below
    the total candidate rows shipped (a genuine stream, not a rename).
    """
    from repro.core import greedy as greedy_mod

    ps, shard, n_srv, f = _case(rng, n_paths=120)
    base, _ = replicate_workload(ps, shard, n_srv, t=2, f=f)
    monkeypatch.setattr(greedy_mod, "_TABLE_STREAM_ROWS", 3)
    forced, fstats = replicate_workload(ps, shard, n_srv, t=2, f=f)
    assert np.array_equal(base.mask, forced.mask)
    assert 0 < fstats.table_peak_rows <= 3
    assert fstats.table_peak_rows < fstats.table_total_rows

    chunk = 40
    chunks = [ps.select(np.arange(i, min(i + chunk, ps.n_paths)))
              for i in range(0, ps.n_paths, chunk)]
    stream = PathStream(iter(chunks))
    _, sstats = replicate_stream(stream, shard, n_srv, t=2, f=f, fused=True)
    assert stream.stats.peak_resident_table_rows == sstats.table_peak_rows
    assert stream.stats.total_table_rows == sstats.table_total_rows
    assert 0 < stream.stats.peak_resident_table_rows <= 3
    assert (
        stream.stats.peak_resident_table_rows
        < stream.stats.total_table_rows
    )


def test_stream_per_chunk_budgets_and_single_use(rng):
    ps, shard, n_srv, f = _case(rng, n_paths=60)
    a, b = ps.select(np.arange(30)), ps.select(np.arange(30, 60))
    stream = PathStream([(a, 1), (b, 3)])
    scheme, stats = replicate_stream(stream, shard, n_srv, f=f, fused=True)
    assert stream.stats.total_paths == 60
    with pytest.raises(RuntimeError, match="single-use"):
        list(stream)
    with pytest.raises(ValueError, match="budget"):
        replicate_stream(PathStream([a]), shard, n_srv)


# ---------------------------------------------------------------------------
# load-aware provisioning (queue_aware + forecast load)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fused", [False, True])
def test_load_forecast_shifts_purchase(fused):
    """Pre-seeded copies of o1/o2 on both s1 and s2; path 0-1-2-3, t=1.

    Load-blind, the walk hops to s1 (home of o1) and finds o2, o3 local —
    served, no purchase.  With s1 forecast hot, the queue-aware walk hops
    to s2 instead and o3 is now a second remote hop — the gate fails and
    the UPDATE, priced under that same walk, buys o3 on s2: the replica
    lands *off* the hot server.
    """
    shard = np.array([0, 1, 2, 1], np.int32)
    ps = PathSet.from_lists([[0, 1, 2, 3]])

    def run(load):
        sch = ReplicationScheme.from_sharding(shard, 3)
        sch.add(np.array([1, 2]), np.array([2, 1]))
        eng = LatencyEngine(sch)
        stats, _ = replicate_delta(
            ps, eng, 1, policy="queue_aware", load=load, fused=fused
        )
        return sch, stats

    cold, cs = run(None)
    hot, hs = run(np.array([0.0, 5.0, 0.0], np.float32))
    assert cs.routed_skips == 1 and cold.mask[3].sum() == 1  # home copy only
    assert hs.routed_skips == 0 and hot.mask[3, 2]
    assert not cold.mask[3, 2]


def test_load_forecast_shifts_workload_level():
    """Same mechanism from a cold start: the first two paths seed
    o1@s2 / o2@s1 (object sizes steer each UPDATE's cheapest candidate),
    which makes the tail path's o1 hop a lookahead *tie* between s1 and
    s2.  Load-blind, the tie resolves to s1 (o1's home), everything is
    local there, and the path is served free.  With s1 forecast hot, the
    queue-aware walk breaks the tie to s2, o3 turns into a second remote
    hop, and the UPDATE — priced under that walk — buys the fix entirely
    on the idle servers: the hot server gains no replicas."""
    shard = np.array([0, 1, 2, 1], np.int32)
    f = np.array([1, 1, 3, 5], np.float32)
    ps = PathSet.from_lists([[2, 1, 2], [3, 2, 3], [0, 1, 2, 3]])
    schemes = {}
    for hot in (False, True):
        load = np.array([0.0, 5.0, 0.0], np.float32) if hot else None
        for fused in (False, True):
            s, st = replicate_workload(
                ps, shard, 3, t=1, f=f, policy="queue_aware", load=load,
                policy_prune=False, fused=fused, batch_size=1,
            )
            schemes[(hot, fused)] = s.mask
            assert st.routed_skips == (0 if hot else 1)
    assert np.array_equal(schemes[(False, False)], schemes[(False, True)])
    assert np.array_equal(schemes[(True, False)], schemes[(True, True)])
    cold, hot = schemes[(False, True)], schemes[(True, True)]
    assert not np.array_equal(cold, hot)
    assert hot[1, 0] and hot[2, 0]           # fix bought on idle s0
    assert np.array_equal(cold[:, 1], hot[:, 1])  # hot s1 gains nothing


# ---------------------------------------------------------------------------
# sharding: mesh == single device
# ---------------------------------------------------------------------------
def test_sharded_equals_single_device(rng):
    if device_count() < 2:
        pytest.skip("single visible device: sharded == single is vacuous")
    ps, shard, n_srv, f = _case(rng)
    single, _ = replicate_workload(
        ps, shard, n_srv, t=2, f=f, policy="nearest_copy", fused=True
    )
    mesh = provisioning_mesh()
    sharded, _ = replicate_workload(
        ps, shard, n_srv, t=2, f=f, policy="nearest_copy", fused=True,
        mesh=mesh,
    )
    assert np.array_equal(single.mask, sharded.mask)


def test_mesh_requires_fused(rng):
    ps, shard, n_srv, f = _case(rng, n_paths=20)
    with pytest.raises(ValueError, match="mesh"):
        replicate_workload(
            ps, shard, n_srv, t=2, f=f, fused=False,
            mesh=provisioning_mesh(),
        )


_SUBPROC = """
import numpy as np
from repro.core.greedy import replicate_workload
from repro.engine.sharding import device_count, provisioning_mesh
from tests.conftest import random_workload

assert device_count() == 4, device_count()
rng = np.random.default_rng(0)
n_srv = 5
ps, shard = random_workload(rng, n_obj=90, n_srv=n_srv, n_paths=110,
                            max_len=6)
f = rng.uniform(0.5, 2.0, 90).astype(np.float32)
for backend in ("jnp", "pallas"):
    single, _ = replicate_workload(ps, shard, n_srv, t=2, f=f,
                                   policy="nearest_copy",
                                   policy_backend=backend, fused=True)
    sharded, _ = replicate_workload(ps, shard, n_srv, t=2, f=f,
                                    policy="nearest_copy",
                                    policy_backend=backend, fused=True,
                                    mesh=provisioning_mesh())
    assert np.array_equal(single.mask, sharded.mask), backend
print("SHARDED_OK")
"""


@pytest.mark.slow
def test_sharded_equals_single_forced_devices():
    """Force 4 host devices in a subprocess and re-check scheme equality
    for both device backends (the in-process test skips on 1-device CI)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root, env.get("PYTHONPATH", "")]
    )
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROC], env=env, cwd=root,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARDED_OK" in out.stdout


# Two budget classes on a 4-device mesh, with every host-driven Pallas
# kernel wrapper (routed gate, revalidation, prune, repair) refusing a
# concrete array placed on more than one device: GSPMD cannot partition a
# Mosaic kernel, so on the chip such an input fails to compile, while
# interpret mode on forced host devices would run it.  Only the fused
# UPDATE loop may hold the words on the mesh, and it must take the
# previous class's load back onto the mesh with them.
_MESH_CLASSES = """
import jax
import numpy as np
from repro.core.greedy import replicate_delta, replicate_workload
from repro.engine import LatencyEngine
from repro.engine import backends
from repro.engine.sharding import device_count, provisioning_mesh
from repro.kernels import path_latency, provision_update, routed_walk
from tests.conftest import random_workload

assert device_count() == 4, device_count()
calls = {}


def one_device(mod, name):
    fn = getattr(mod, name)

    def guarded(*args, **kwargs):
        for a in jax.tree_util.tree_leaves((args, kwargs)):
            if isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer):
                assert len(a.sharding.device_set) == 1, (
                    f"{name} got an array on {len(a.sharding.device_set)} devices"
                )
        calls[name] = calls.get(name, 0) + 1
        return fn(*args, **kwargs)

    setattr(mod, name, guarded)


for mod, name in [
    (backends, "path_latency_pallas"),
    (path_latency, "path_latency_pallas"),
    (routed_walk, "routed_walk_pallas"),
    (routed_walk, "scored_walk_pallas"),
    (provision_update, "fused_update_pallas"),
]:
    one_device(mod, name)

rng = np.random.default_rng(0)
n_srv, n_obj, nq = 5, 90, 30
ps, shard = random_workload(rng, n_obj=n_obj, n_srv=n_srv, n_paths=110,
                            max_len=6, n_queries=nq)
t = rng.integers(1, 3, nq)                    # budget classes t = 1 and 2
assert set(np.unique(t)) == {1, 2}
delta, _ = random_workload(rng, n_obj=n_obj, n_srv=n_srv, n_paths=60,
                           max_len=6, n_queries=nq)
f = rng.uniform(0.5, 2.0, n_obj).astype(np.float32)
mesh = provisioning_mesh()
for backend in ("jnp", "pallas"):
    kw = dict(f=f, policy="nearest_copy", policy_backend=backend, fused=True)
    single, _ = replicate_workload(ps, shard, n_srv, t, **kw)
    sharded, _ = replicate_workload(ps, shard, n_srv, t, mesh=mesh, **kw)
    assert np.array_equal(single.mask, sharded.mask), backend
    adds = [
        replicate_delta(delta, LatencyEngine(single.copy()), t, mesh=m, **kw)[1]
        for m in (None, mesh)
    ]
    for a, b in zip(*adds):
        assert np.array_equal(a, b), backend
assert calls.get("routed_walk_pallas"), calls
assert calls.get("fused_update_pallas"), calls
print("MESH_CLASSES_OK")
"""


def test_mesh_budget_classes_keep_kernels_on_one_device():
    """Two budget classes through the mesh driver on 4 forced host devices
    equal the one-device run, and no host-driven kernel gets a mesh-placed
    array (see ``_MESH_CLASSES``)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root, env.get("PYTHONPATH", "")]
    )
    out = subprocess.run(
        [sys.executable, "-c", _MESH_CLASSES], env=env, cwd=root,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH_CLASSES_OK" in out.stdout


# ---------------------------------------------------------------------------
# tier-1 wall-clock guard
# ---------------------------------------------------------------------------
def test_default_grid_point_within_budget():
    """The benchmark's default grid point (smoke SNB union, fused arm,
    cold compile) must finish inside its stated budget — a dispatch-count
    regression (e.g. re-introducing per-batch host syncs) blows this long
    before it breaks parity."""
    secs, mask = default_grid_point()
    assert mask.any()
    assert secs < DEFAULT_BUDGET_S, (
        f"default grid point took {secs:.1f}s (budget {DEFAULT_BUDGET_S}s)"
    )
