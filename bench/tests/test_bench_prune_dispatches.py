"""The per-layer metric read from the prune's dispatch counter: its
reader on a synthetic window, its entry, and a traced run at a tiny size
on the CPU that reports it."""
import contextlib
import io
import json

import jax
import pytest

from bench.harness import cell as cells
from bench.harness import report
from bench.tests.test_bench_drivers import PROVISION, _tiny

NAME = "greedy.prune_dispatches_per_kpath.provision"
COUNTER = "repro.greedy.prune.dispatches"


def test_reader_divides_the_counter_by_the_kpaths():
    reader = cells.metric_reader(NAME)
    ctx = {"counters": {COUNTER: 17.0, "repro.greedy.prune.candidates": 1e3},
           "summary": {"paths_processed": 3072}}
    assert reader.read(ctx) == pytest.approx(17.0 * 1000.0 / 3072)
    # a program without the counter reads nothing, and does not raise
    assert reader.read(dict(ctx, counters={})) is None
    assert reader.read(dict(ctx, summary={"paths_processed": 0})) is None


def test_the_metric_is_registered_on_the_provision_cell():
    m = {m["name"]: m for m in cells.load_spec()["per_layer"]}[NAME]
    assert m["workloads"] == ["snb_sf1.provision"]
    assert m["moves"] == "provision_paths_per_s"
    assert (m["unit"], m["better"], m["source"]) == (
        "count", "lower", "program_counter")
    assert m["layer"] == "policy prune and re-pack (core/replication)"


def test_a_traced_run_reports_it(monkeypatch):
    import bench.run as run
    from repro import obs

    c = _tiny(PROVISION[0])
    drv = cells.driver(c)
    c.per_layer = [m for m in cells.load_spec()["per_layer"]
                   if m["name"] in (NAME, "greedy.syncs_per_kpath.provision")]
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
    got = {k: v["value"] for k, v in res["metrics"].items()}
    # a few groups, each one dispatch and one readback of its verdicts
    assert 0 < got[NAME] < got["greedy.syncs_per_kpath.provision"]
