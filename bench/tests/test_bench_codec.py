"""The per-layer metrics read from the host codec's span
(``repro.engine.codec``): each reader on a synthetic window, the entries
that register them on every provisioning cell, and a traced run at a tiny
size on the CPU that reports them."""
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
    "engine.codec_ms_per_kpath.provision": ("repro.engine.codec.ns", 1e-6),
    "engine.codec_passes_per_kpath.provision": ("repro.engine.codec.n", 1.0),
}
CELLS = ["snb_sf1.provision", "snb_sf1_k1.provision", "snb_sf1.provision_hf"]


@pytest.mark.parametrize("name", sorted(READS))
def test_reader_divides_the_counter_by_the_kpaths(name):
    counter, scale = READS[name]
    reader = cells.metric_reader(name)
    ctx = {"counters": {counter: 6_000_000.0, "repro.other.ns": 1.0},
           "summary": {"paths_processed": 3000}}
    assert reader.read(ctx) == pytest.approx(scale * 2_000_000.0)
    # a program without the span reads nothing, and does not raise
    assert reader.read(dict(ctx, counters={"repro.other.ns": 1.0})) is None
    assert reader.read(dict(ctx, counters={counter: 0})) is None
    assert reader.read(dict(ctx, summary={"paths_processed": 0})) is None


@pytest.mark.parametrize("name", sorted(READS))
def test_the_metric_is_registered_on_every_provision_cell(name):
    counter, _ = READS[name]
    m = {m["name"]: m for m in cells.load_spec()["per_layer"]}[name]
    assert m["workloads"] == CELLS
    assert m["layer"] == "host unpack of the packed words (engine/packed)"
    assert m["moves"] == "provision_paths_per_s"
    assert (m["better"], m["source"]) == ("lower", "program_span")
    assert m["unit"] == ("ms" if counter.endswith(".ns") else "count")


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
