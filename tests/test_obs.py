"""Telemetry plane: metrics registry, span tracing, burn-rate blame.

Covers the invariants the observability layer promises:

  * histogram percentiles within one log bucket of the exact order
    statistic, on adversarial inputs (bucket-edge values, heavy tails);
  * histogram merge is *exact parity* with single-stream recording;
  * the simulator emits one span per served access, and along a linear
    walk the span queue+service durations plus the coordinator barrier
    sum exactly to the query's simulated latency (jitter off);
  * tail-biased sampling never drops a violating query's trace;
  * burn-rate attribution names the constructed hotspot server, both
    directly and through the adaptive controller's repair report;
  * ``TRANSFER.scope()`` isolates and restores transfer accounting;
  * ``replicate_stream``'s double-buffered ingestion provisions the
    same scheme as eager chunked deltas and reports the overlap gauge.
"""
import json

import numpy as np
import pytest

from repro import obs
from repro.core import ReplicationScheme, replicate_workload
from repro.core.paths import PathSet
from repro.distsys import Cluster, LatencyModel, execute_workload
from repro.engine import TRANSFER
from repro.obs import (
    Counter,
    Histogram,
    MetricsRegistry,
    Tracer,
    attribute_burn,
    chrome_trace,
    install_compile_hook,
)
from repro.serve import AdaptiveController, ControllerConfig, simulate
from tests.conftest import random_workload


@pytest.fixture
def obs_on():
    """Enable the plane with a clean registry; restore on exit."""
    was = obs.enabled()
    obs.REGISTRY.reset()
    obs.enable()
    try:
        yield obs.REGISTRY
    finally:
        (obs.enable if was else obs.disable)()
        obs.REGISTRY.reset()


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    c = reg.counter("a.b")
    c.inc(3)
    assert reg.counter("a.b") is c          # get-or-create returns same obj
    assert reg.counter("a.b").value == 3
    reg.gauge("a.g").set(2.5)
    reg.histogram("a.h").record(10.0)
    assert reg.names() == ["a.b", "a.g", "a.h"]
    with pytest.raises(TypeError, match="already a"):
        reg.gauge("a.b")
    with pytest.raises(TypeError, match="already a"):
        reg.counter("a.h")
    snap = reg.snapshot()
    assert snap["a.b"] == 3 and snap["a.g"] == 2.5
    assert snap["a.h"]["count"] == 1
    json.dumps(snap)                        # artifact must be serializable
    reg.reset()
    assert reg.names() == []


@pytest.mark.parametrize(
    "values",
    [
        np.random.default_rng(0).lognormal(3.0, 1.5, 5000),   # heavy tail
        np.random.default_rng(1).pareto(1.5, 5000) + 1.0,     # heavier tail
        np.full(100, 42.0),                                   # degenerate
        1.0 * 1.1 ** np.arange(200),                          # exact edges
        np.concatenate([np.full(99, 1.0), [1e9]]),            # one outlier
    ],
)
def test_histogram_percentile_within_one_bucket(values):
    h = Histogram("t", lo=1.0, growth=1.1)
    h.record_many(values)
    for q in (50.0, 90.0, 99.0):
        exact = float(np.percentile(values, q, method="inverted_cdf"))
        got = h.percentile(q)
        # upper-edge convention: exact sits in the reported bucket, i.e.
        # within one multiplicative bucket width below the edge
        assert got / h.growth <= exact * (1 + 1e-9), (q, got, exact)
        assert exact <= got * (1 + 1e-9), (q, got, exact)
    assert h.n == len(values)
    assert h.max == pytest.approx(float(np.max(values)))


def test_histogram_scalar_vs_vector_recording_identical():
    vals = np.random.default_rng(2).lognormal(2.0, 1.0, 777)
    a = Histogram("a", lo=0.5, growth=1.2)
    b = Histogram("b", lo=0.5, growth=1.2)
    a.record_many(vals)
    for v in vals:
        b.record(float(v))
    assert a.counts == b.counts and a.n == b.n


def test_histogram_merge_exact_parity():
    rng = np.random.default_rng(3)
    x, y = rng.lognormal(2, 1, 400), rng.pareto(2.0, 600) + 0.1
    h1 = Histogram("h", lo=0.1, growth=1.1)
    h2 = Histogram("h", lo=0.1, growth=1.1)
    ref = Histogram("h", lo=0.1, growth=1.1)
    h1.record_many(x)
    h2.record_many(y)
    ref.record_many(np.concatenate([x, y]))
    m = h1.merge(h2)
    assert m.counts == ref.counts
    assert m.n == ref.n and m.sum == pytest.approx(ref.sum)
    for q in (50.0, 99.0, 99.9):
        assert m.percentile(q) == ref.percentile(q)  # bit-identical
    with pytest.raises(ValueError, match="geometry"):
        h1.merge(Histogram("h", lo=0.1, growth=1.2))


def test_compile_hook_counts_jit_cache_misses():
    import jax

    counter = install_compile_hook()
    assert isinstance(counter, Counter)
    before = counter.value

    @jax.jit
    def _fresh(x):
        return x * 3 + 1

    _fresh(np.arange(7))                    # cache miss: compiles
    assert counter.value >= before + 1
    mid = counter.value
    _fresh(np.arange(7))                    # cache hit: no event
    assert counter.value == mid


# ---------------------------------------------------------------------------
# span tracing (simulator)
# ---------------------------------------------------------------------------
def _traced_run(rng, rate_qps=1.0, jitter=0.0, budget=1e12, **kw):
    ps, shard = random_workload(rng, n_paths=150, n_queries=60)
    scheme, _ = replicate_workload(ps, shard, 5, t=2)
    cluster = Cluster(scheme)
    model = LatencyModel(jitter_sigma=jitter)
    tr = Tracer(budget_us=budget)
    rep = simulate(
        cluster, ps, rate_qps=rate_qps, model=model, seed=4, trace=tr, **kw
    )
    return ps, rep, tr, model


def test_one_span_per_served_access(rng):
    ps, rep, tr, _ = _traced_run(rng)
    # the access tree dedups shared prefixes: expected span count is the
    # number of unique path prefixes per query
    expected = 0
    prefixes: dict[int, set] = {}
    for p in range(ps.n_paths):
        q = int(ps.query_ids[p])
        seen = prefixes.setdefault(q, set())
        pref = ()
        for x in range(int(ps.lengths[p])):
            pref = pref + (int(ps.objects[p, x]),)
            if pref not in seen:
                seen.add(pref)
                expected += 1
    assert tr.n_spans == expected
    # near-zero load: every kept trace's spans show no queue wait
    for t in tr.traces:
        for s in t.spans:
            assert s.queue_wait_us == pytest.approx(0.0)
            assert s.server >= 0


def test_linear_walk_spans_sum_to_latency(rng):
    """Along a linear walk, queue+service spans + coordinator == latency."""
    ps, rep, tr, model = _traced_run(rng, jitter=0.0)
    checked = 0
    for t in tr.traces:
        spans = t.spans
        if not spans:
            continue
        starts = sorted(s.t_start_us for s in spans)
        ends = sorted(s.t_end_us for s in spans)
        linear = all(e <= s2 + 1e-9 for e, s2 in zip(ends[:-1], starts[1:]))
        if linear:
            total = sum(s.queue_wait_us + s.service_us for s in spans)
            assert total + model.coordinator_us == pytest.approx(
                t.latency_us
            )
            checked += 1
    assert checked > 0, "workload produced no linear walks to check"


def test_tracing_does_not_perturb_simulation(rng):
    ps, shard = random_workload(rng, n_paths=200, n_queries=80)
    scheme, _ = replicate_workload(ps, shard, 5, t=2)
    cluster = Cluster(scheme)
    rep0 = simulate(cluster, ps, rate_qps=50_000, seed=9)
    rep1 = simulate(
        cluster, ps, rate_qps=50_000, seed=9, trace=Tracer(budget_us=100.0)
    )
    assert np.array_equal(rep0.latency_us, rep1.latency_us)


def test_tail_bias_never_drops_violators(rng):
    ps, shard = random_workload(rng, n_paths=300, n_queries=120)
    scheme, _ = replicate_workload(ps, shard, 5, t=2)
    cluster = Cluster(scheme)
    rep0 = simulate(cluster, ps, rate_qps=300_000, seed=5, concurrency=4)
    p80 = float(np.percentile(rep0.latency_us, 80.0))
    # tiny head+ring so sampling pressure is real: violators must survive
    tr = Tracer(budget_us=p80, head=2, ring=4)
    rep = simulate(
        cluster, ps, rate_qps=300_000, seed=5, concurrency=4, trace=tr
    )
    violators = set(np.nonzero(rep.latency_us > p80)[0].tolist())
    assert len(violators) > 4, "need more violators than the ring holds"
    assert tr.n_violations == len(violators)
    kept = {t.query for t in tr.traces}
    assert violators <= kept
    assert all(t.violated for t in tr.violations)
    assert len(tr.traces) <= 2 + 4 + len(violators)
    # non-violators ARE sampled away under this pressure
    assert len(kept) < ps.n_queries


def test_tracer_reused_across_runs_accumulates(rng):
    ps, shard = random_workload(rng, n_paths=100, n_queries=40)
    scheme, _ = replicate_workload(ps, shard, 5, t=2)
    cluster = Cluster(scheme)
    tr = Tracer(budget_us=1e12)
    simulate(cluster, ps, rate_qps=1000, seed=1, trace=tr)
    simulate(cluster, ps, rate_qps=1000, seed=2, trace=tr)
    assert tr.n_completed == 2 * ps.n_queries


def test_chrome_trace_export(rng, tmp_path):
    _, _, tr, _ = _traced_run(rng, rate_qps=100_000)
    out = tmp_path / "trace.json"
    blob = tr.chrome_trace(str(out))
    loaded = json.loads(out.read_text())
    assert loaded == blob
    events = blob["traceEvents"]
    slices = [e for e in events if e["ph"] == "X"]
    assert slices, "no slices exported"
    for e in slices:
        assert e["dur"] >= 0 and e["ts"] >= 0
        assert {"query", "hop", "object", "why"} <= set(e["args"])
    names = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert any(n.startswith("server-") for n in names)


# ---------------------------------------------------------------------------
# structural spans (closed-form executor)
# ---------------------------------------------------------------------------
def test_executor_structural_spans(rng):
    ps, shard = random_workload(rng, n_paths=120, n_queries=50)
    scheme, _ = replicate_workload(ps, shard, 5, t=2)
    tr = Tracer()
    rep = execute_workload(Cluster(scheme), ps, LatencyModel(), seed=1,
                           trace=tr)
    assert tr.n_completed == ps.n_queries
    # same shared-prefix dedup as the simulator: span counts match
    sim_tr = Tracer()
    simulate(Cluster(scheme), ps, rate_qps=1.0, seed=1, trace=sim_tr)
    assert tr.n_spans == sim_tr.n_spans


# ---------------------------------------------------------------------------
# burn-rate attribution (the acceptance-criterion hotspot)
# ---------------------------------------------------------------------------
def _hotspot_case(rng, n_queries=120):
    """Every query walks hot(s0) -> spread(s1/s2) -> hot(s0): server 0
    serves 2/3 of all traffic and owns the queue, and the walk makes two
    distributed traversals (h=2), so a t=1 controller must repair."""
    n_obj = 30
    shard = np.zeros(n_obj, np.int32)
    shard[20:] = rng.integers(1, 3, 10)      # objects 20.. on servers 1/2
    paths = [
        [int(rng.integers(0, 10)), int(rng.integers(20, n_obj)),
         int(rng.integers(10, 20))]
        for _ in range(n_queries)
    ]
    ps = PathSet.from_lists(paths, list(range(n_queries)))
    scheme = ReplicationScheme.from_sharding(shard, 3)
    return ps, shard, scheme


def test_burn_attribution_names_hotspot_server(rng):
    ps, shard, scheme = _hotspot_case(rng)
    cluster = Cluster(scheme)
    rep0 = simulate(cluster, ps, rate_qps=400_000, seed=3, concurrency=2)
    p90 = float(np.percentile(rep0.latency_us, 90.0))
    tr = Tracer(budget_us=p90)
    rep = simulate(
        cluster, ps, rate_qps=400_000, seed=3, concurrency=2, trace=tr
    )
    assert tr.n_violations > 0
    burn = attribute_burn(tr, allowed_frac=0.01)
    tb = burn["default"]
    assert tb.n_violations == tr.n_violations
    assert tb.burn_rate > 1.0               # 10% violating >> 1% allowed
    # the acceptance check: blame names the constructed hotspot, and the
    # violators' worst hops point at it too
    assert tb.top_server() == 0
    assert tb.blame_queue_us[0] == max(tb.blame_queue_us.values())
    worst = [h.server for h in tb.worst_hops]
    assert worst and worst.count(0) >= len(worst) // 2
    # every worst hop names a hop/server/share a human can read
    for h in tb.worst_hops:
        assert 0.0 <= h.share <= 1.0 + 1e-9
        assert h.latency_us > h.budget_us


def test_controller_report_carries_blame(rng):
    """A repair triggered on the hotspot explains itself: report.blame
    names the server whose queue ate the violators' budgets."""
    ps, shard, scheme = _hotspot_case(rng)
    cluster = Cluster(scheme)
    rep0 = simulate(cluster, ps, rate_qps=400_000, seed=3, concurrency=2)
    p90 = float(np.percentile(rep0.latency_us, 90.0))
    tr = Tracer(budget_us=p90)
    rep = simulate(
        cluster, ps, rate_qps=400_000, seed=3, concurrency=2, trace=tr
    )
    controller = AdaptiveController(
        cluster, ControllerConfig(t=1, window=512, min_queries=32)
    )
    report = controller.observe(ps, latency_us=rep.latency_us, trace=tr)
    assert report is not None, "3-hop paths at t=1 must trigger a repair"
    assert report.blame is not None
    blame = report.blame["default"]
    assert blame["top_server"] == 0
    assert blame["burn_rate"] > 1.0
    # untraced observe keeps the legacy report shape
    ctl2 = AdaptiveController(
        Cluster(ReplicationScheme.from_sharding(shard, 3)),
        ControllerConfig(t=1, window=512, min_queries=32),
    )
    rep2 = ctl2.observe(ps, latency_us=rep.latency_us)
    assert rep2 is not None and rep2.blame is None


# ---------------------------------------------------------------------------
# TRANSFER.scope
# ---------------------------------------------------------------------------
def test_transfer_scope_isolates_and_restores():
    base = TRANSFER.h2d_bytes
    with TRANSFER.scope():
        TRANSFER.h2d_bytes += 100
        TRANSFER.h2d_calls += 1
        with TRANSFER.scope():              # nesting isolates each level
            assert TRANSFER.h2d_bytes == 0
            TRANSFER.h2d_bytes += 7
        assert TRANSFER.h2d_bytes == 107    # inner totals restored
    assert TRANSFER.h2d_bytes == base + 107


def test_transfer_scope_restores_on_exception():
    base = TRANSFER.h2d_bytes
    with pytest.raises(RuntimeError):
        with TRANSFER.scope():
            TRANSFER.h2d_bytes += 11
            raise RuntimeError("boom")
    assert TRANSFER.h2d_bytes == base + 11


# ---------------------------------------------------------------------------
# provisioning telemetry + pipelined streaming
# ---------------------------------------------------------------------------
def test_stream_pipeline_matches_eager_and_reports_overlap(rng, obs_on):
    from repro.core import replicate_delta, replicate_stream
    from repro.engine import LatencyEngine, PathStream

    ps, shard = random_workload(rng, n_paths=160, n_queries=80)
    chunk = 40
    chunks = [
        ps.select(np.arange(i, min(i + chunk, ps.n_paths)))
        for i in range(0, ps.n_paths, chunk)
    ]
    scheme_d = ReplicationScheme.from_sharding(shard, 5)
    eng = LatencyEngine(scheme_d)
    for c in chunks:
        replicate_delta(c, eng, 2, fused=True)
    # the eager deltas above each drained their own device stats; clear
    # the registry so the readback assertion below sees only the stream's
    obs_on.reset()

    def gen():
        yield from chunks

    scheme_s, stats = replicate_stream(
        PathStream(gen()), shard, 5, t=2, fused=True
    )
    assert np.array_equal(scheme_d.mask, scheme_s.mask)
    assert stats.ingest_overlap_s >= 0.0
    # the fused stream defers its device stats: ONE readback at the end
    snap = obs_on.snapshot()
    assert snap["repro.greedy.stat_readbacks"] == 1
    assert snap["repro.stream.chunks"] == len(chunks)
    assert "repro.stream.ingest_overlap_s" in snap
    # per-class provisioning timeline rode along
    assert stats.timeline, "obs-enabled run must carry a greedy timeline"
    for row in stats.timeline:
        assert {"budget", "n_vec", "n_seq", "n_candidates",
                "routed_skips"} <= set(row)


def test_simulator_registers_serve_metrics(rng, obs_on):
    ps, shard = random_workload(rng, n_paths=100, n_queries=40)
    scheme, _ = replicate_workload(ps, shard, 5, t=2)
    simulate(Cluster(scheme), ps, rate_qps=10_000, seed=1)
    snap = obs_on.snapshot()
    assert snap["repro.serve.queries"] == ps.n_queries
    assert snap["repro.serve.latency_us"]["count"] == ps.n_queries
    assert snap["repro.serve.latency_us"]["p99"] > 0


def test_disabled_plane_registers_nothing(rng):
    obs.disable()
    obs.REGISTRY.reset()
    ps, shard = random_workload(rng, n_paths=60, n_queries=25)
    scheme, stats = replicate_workload(ps, shard, 5, t=2)
    simulate(Cluster(scheme), ps, rate_qps=10_000, seed=1)
    # the k-resilience gate's spans and counters too
    replicate_workload(ps, shard, 5, t=1, policy="nearest_copy",
                       resilience=1)
    # the jit compile hook is a process-global JAX listener (cannot be
    # uninstalled), so its counter may reappear; nothing else may
    assert [n for n in obs.REGISTRY.names()
            if n != "repro.jit.compiles"] == []
    assert stats.timeline is None


# ---------------------------------------------------------------------------
# host spans + the readback counter of the provisioner
# ---------------------------------------------------------------------------
PHASES = ("provision", "init", "classes", "revalidate", "unpack", "prune")


def test_span_with_the_plane_off_is_a_shared_no_op():
    obs.disable()
    obs.REGISTRY.reset()
    a, b = obs.span("repro.test.a"), obs.span("repro.test.b", paths=3)
    assert a is b
    with a, b:
        pass
    assert obs.REGISTRY.names() == []


def test_span_times_into_the_registry_and_shares_the_call(obs_on):
    from repro.obs import spans

    with obs.span("repro.test.outer", paths=7) as outer:
        with obs.span("repro.test.inner") as inner:
            with obs.span("repro.test.inner") as inner2:
                pass
    with obs.span("repro.test.outer") as again:
        pass
    assert inner.parent is outer and inner2.parent is inner
    assert outer.parent is None and again.parent is None
    # the outer span starts a call; every span nested in it carries its id
    assert outer.args == {"paths": 7, "call": outer.args["call"]}
    assert inner.args["call"] == inner2.args["call"] == outer.args["call"]
    assert again.args["call"] != outer.args["call"]
    snap = obs_on.snapshot()
    assert snap["repro.test.outer.n"] == 2
    assert snap["repro.test.inner.n"] == 2
    # inclusive: the parent's time holds its children's
    assert snap["repro.test.outer.ns"] >= snap["repro.test.inner.ns"] > 0
    assert spans._stack() == []


def test_span_closes_on_exception(obs_on):
    from repro.obs import spans

    with pytest.raises(ValueError):
        with obs.span("repro.test.boom"):
            raise ValueError("x")
    assert obs_on.snapshot()["repro.test.boom.n"] == 1
    assert spans._stack() == []


def test_provision_records_a_span_per_phase(rng, obs_on):
    ps, shard = random_workload(rng, n_paths=150, n_queries=60)
    _, stats = replicate_workload(ps, shard, 5, t=1, policy="nearest_copy")
    assert stats.pruned_replicas > 0  # the prune and its re-pack ran
    snap = obs_on.snapshot()
    for phase in PHASES + ("plan", "filter", "batches", "prune.pack",
                           "prune.sweep", "prune.repack"):
        assert snap[f"repro.greedy.{phase}.ns"] > 0, phase
        assert snap[f"repro.greedy.{phase}.n"] >= 1, phase
    assert snap["repro.greedy.provision.n"] == 1
    # one span per class, not per batch
    assert snap["repro.greedy.batches.n"] == snap["repro.greedy.plan.n"]
    # the phases nest in the call
    assert snap["repro.greedy.provision.ns"] >= sum(
        snap[f"repro.greedy.{p}.ns"] for p in PHASES[1:])


def test_delta_and_stream_open_their_call_spans(rng, obs_on):
    from repro.core import replicate_delta, replicate_stream
    from repro.engine import LatencyEngine

    ps, shard = random_workload(rng, n_paths=80, n_queries=30)
    replicate_delta(ps, LatencyEngine(ReplicationScheme.from_sharding(
        shard, 5)), 1, policy="nearest_copy")
    replicate_stream([ps.select(np.arange(40)),
                      ps.select(np.arange(40, 80))], shard, 5, t=1)
    snap = obs_on.snapshot()
    assert snap["repro.greedy.delta.n"] == 3  # one, then one per chunk
    assert snap["repro.greedy.stream.n"] == 1
    assert snap["repro.greedy.revalidate.n"] == 1
    assert snap["repro.greedy.unpack.n"] == 1  # the stream's end sync


@pytest.mark.parametrize("policy,passes", [("home_first", 1),
                                            ("nearest_copy", 3)])
def test_provision_counts_its_codec_passes(rng, obs_on, policy, passes):
    """A call's whole-mask encodes and decodes: home_first decodes once;
    nearest_copy decodes, then its prune packs the mask into an engine
    and re-packs what it kept."""
    ps, shard = random_workload(rng, n_paths=150, n_queries=60)
    _, stats = replicate_workload(ps, shard, 5, t=1, policy=policy)
    assert stats.fallback_paths == 0
    assert (stats.pruned_replicas > 0) == (policy == "nearest_copy")
    snap = obs_on.snapshot()
    assert snap["repro.engine.codec.n"] == passes
    assert snap["repro.engine.codec.ns"] > 0


RESILIENCE = ("homes", "eval", "unpack", "repair", "replay")


@pytest.mark.parametrize("entry", ["workload", "delta"])
def test_resilience_spans_tile_the_phase_and_count_its_work(rng, obs_on,
                                                            entry):
    from repro.core import replicate_delta
    from repro.engine import LatencyEngine

    ps, shard = random_workload(rng, n_obj=200, n_srv=6, n_paths=120,
                                n_queries=50)

    def replicas(resilience):
        if entry == "delta":
            eng = LatencyEngine(ReplicationScheme.from_sharding(shard, 6))
            return replicate_delta(ps, eng, 1, policy="nearest_copy",
                                   resilience=resilience)[0].replicas
        return replicate_workload(ps, shard, 6, t=1, policy="nearest_copy",
                                  resilience=resilience)[1].replicas

    before = replicas(None)
    obs_on.reset()
    after = replicas(1)
    snap = obs_on.snapshot()
    phase = snap["repro.greedy.resilience.ns"]
    children = sum(snap.get(f"repro.greedy.resilience.{c}.ns", 0)
                   for c in RESILIENCE)
    assert snap["repro.greedy.resilience.repair.n"] >= 1
    assert 0.97 * phase <= children <= phase
    assert snap["repro.greedy.resilience.cases"] == (
        6 * snap["repro.greedy.resilience.eval.n"])
    assert snap["repro.greedy.resilience.violations"] > 0
    assert snap["repro.greedy.resilience.additions"] == after - before > 0


@pytest.mark.parametrize("backend", ["jnp", "reference"])
def test_prune_counts_its_dispatches_and_candidates(rng, monkeypatch,
                                                    backend):
    from repro.core import replication

    ps, shard = random_workload(rng, n_paths=150, n_queries=60)
    scheme, _ = replicate_workload(ps, shard, 5, t=1, policy="nearest_copy",
                                   policy_prune=False)
    n_cand = int(scheme.mask.sum()) - len(shard)
    groups = []
    grouping = replication._independent_groups

    def spy(*a):
        groups[:] = grouping(*a)
        return groups

    monkeypatch.setattr(replication, "_independent_groups", spy)

    def prune():
        return replication.prune_scheme_replicas(
            ReplicationScheme(scheme.mask.copy(), shard), ps, 1,
            policy="nearest_copy", backend=backend, group_max=4)

    was = obs.enabled()
    try:
        obs.REGISTRY.reset()
        obs.enable()
        on = prune()
        snap = obs.REGISTRY.snapshot()
        obs.disable()
        obs.REGISTRY.reset()
        off = prune()
        names = obs.REGISTRY.names()
    finally:
        (obs.enable if was else obs.disable)()
        obs.REGISTRY.reset()
    assert on == off and on[0] > 0
    assert snap["repro.greedy.prune.candidates"] == n_cand
    if backend == "reference":  # the serial oracle: one gate per candidate
        assert groups == []
        assert snap["repro.greedy.prune.dispatches"] == n_cand
    else:  # one dispatch per group; group_max 4 forces several
        assert 1 < len(groups) < n_cand
        assert snap["repro.greedy.prune.dispatches"] == len(groups)
    assert not [n for n in names if n.startswith("repro.greedy.prune")]


@pytest.fixture
def readbacks(monkeypatch):
    """Count device->host readbacks made through ``to_host`` and any made
    elsewhere (``np.asarray`` / ``np.array`` of a device array, or a
    Python scalar or list taken from one), with the frame that made it."""
    import sys

    import jax
    from jax._src.array import ArrayImpl

    from repro.engine import streaming

    seen = {"to_host": 0, "elsewhere": []}
    to_host_code = streaming.to_host.__code__

    def note(depth):
        f = sys._getframe(depth)
        if f.f_code is to_host_code:
            seen["to_host"] += 1
            return
        while f is not None and "repro" not in f.f_code.co_filename:
            f = f.f_back
        where = (f"{f.f_code.co_filename}:{f.f_lineno}" if f is not None
                 else "outside repro")
        seen["elsewhere"].append(where)

    def wrap(fn):
        def conv(a, *args, **kw):
            if isinstance(a, jax.Array):
                note(2)
            return fn(a, *args, **kw)

        return conv

    monkeypatch.setattr(np, "asarray", wrap(np.asarray))
    monkeypatch.setattr(np, "array", wrap(np.array))
    value = ArrayImpl._value

    def _value(self):
        note(3)
        return value.fget(self)

    monkeypatch.setattr(ArrayImpl, "_value", property(_value))
    return seen


@pytest.mark.parametrize("case", ["default", "fused", "delta", "resilient"])
def test_every_provisioning_readback_goes_through_to_host(rng, readbacks,
                                                          case):
    from repro.core import replicate_delta
    from repro.engine import LatencyEngine

    ps, shard = random_workload(rng, n_paths=120, n_queries=50)

    def provision():
        if case == "delta":
            eng = LatencyEngine(ReplicationScheme.from_sharding(shard, 5))
            replicate_delta(ps, eng, 1, policy="nearest_copy")
            return eng.packed.unpack()
        scheme, _ = replicate_workload(
            ps, shard, 5, t=1, policy="nearest_copy",
            fused=case == "fused",
            resilience=1 if case == "resilient" else None)
        return scheme.mask

    was = obs.enabled()
    obs.disable()
    try:
        off = provision()
        obs.REGISTRY.reset()
        readbacks["to_host"] = 0
        readbacks["elsewhere"].clear()
        obs.enable()
        on = provision()
        calls = obs.REGISTRY.snapshot()["repro.engine.d2h_calls"]
    finally:
        (obs.enable if was else obs.disable)()
        obs.REGISTRY.reset()
    # the plane changes nothing the provisioner computes
    assert np.array_equal(off, on)
    assert readbacks["elsewhere"] == []
    # one count per to_host call, and the unpack the test itself made
    assert calls == readbacks["to_host"] > 1 + (case == "delta")
