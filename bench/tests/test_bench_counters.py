"""The per-layer metrics read from the program's spans and readback
counter: each reader on a synthetic window, the entries that register
them, and a traced run at a tiny size on the CPU that reports them."""
import contextlib
import io
import json

import jax
import pytest

from bench.harness import cell as cells
from bench.harness import report
from bench.tests.test_bench_drivers import PROVISION, _tiny

# metric -> (counter it reads, scale to its unit)
READS = {
    "greedy.init_ms_per_kpath.provision": ("repro.greedy.init.ns", 1e-6),
    "greedy.classes_ms_per_kpath.provision": ("repro.greedy.classes.ns", 1e-6),
    "greedy.revalidate_ms_per_kpath.provision": (
        "repro.greedy.revalidate.ns", 1e-6),
    "greedy.unpack_ms_per_kpath.provision": ("repro.greedy.unpack.ns", 1e-6),
    "greedy.prune_ms_per_kpath.provision": ("repro.greedy.prune.ns", 1e-6),
    "greedy.syncs_per_kpath.provision": ("repro.engine.d2h_calls", 1.0),
}


@pytest.mark.parametrize("name", sorted(READS))
def test_reader_divides_the_counter_by_the_kpaths(name):
    counter, scale = READS[name]
    reader = cells.metric_reader(name)
    ctx = {"counters": {counter: 6_000_000.0, "repro.other.ns": 1.0},
           "summary": {"paths_processed": 3000}}
    assert reader.read(ctx) == pytest.approx(scale * 2_000_000.0)
    # nothing to read: the counter did not move, or no path was processed
    assert reader.read(dict(ctx, counters={"repro.other.ns": 1.0})) is None
    assert reader.read(dict(ctx, counters={counter: 0})) is None
    assert reader.read(dict(ctx, summary={"paths_processed": 0})) is None


def test_each_metric_is_registered_on_the_provision_cell():
    entries = {m["name"]: m for m in cells.load_spec()["per_layer"]}
    for name, (counter, _) in READS.items():
        m = entries[name]
        assert m["workloads"] == ["snb_sf1.provision"]
        assert m["moves"] == "provision_paths_per_s"
        assert m["better"] == "lower"
        assert m["source"] == ("program_counter" if counter.endswith("calls")
                               else "program_span")
        assert m["unit"] == ("count" if counter.endswith("calls") else "ms")


def test_a_traced_run_reports_them(monkeypatch):
    import bench.run as run
    from repro import obs

    c = _tiny(PROVISION[0])
    drv = cells.driver(c)
    c.per_layer = [m for m in cells.load_spec()["per_layer"]
                   if m["name"] in READS]
    monkeypatch.setattr(report, "peaks",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    was = obs.enabled()
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            assert run.run(c, drv, 0.05, True, jax.devices()[:1]) == 0
    finally:
        (obs.enable if was else obs.disable)()
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] is True
    assert set(res["metrics"]) == set(READS)
    assert all(m["value"] > 0 for m in res["metrics"].values())
