"""Each driver's window at a tiny size on the CPU: what the program
produces is judged correct; the contract's control, and the program with
its timed path broken, are judged not correct."""
import contextlib
import io
import json

import jax
import pytest

from bench.gen import data as gen_data
from bench.harness import cell as cells
from bench.harness import faults, report


def _span(name):
    return contextlib.nullcontext()


# every traffic mix under bench/: (configuration file, traffic mix)
PROVISION = [("bench/configs/snb_sf1.json", "provision_nc")]
E2E = [{"name": "provision_paths_per_s", "unit": "paths/s"},
       {"name": "setup_s", "unit": "s"}]
TINY = {"person": 300, "forum": 200, "post": 3000, "comment": 6000,
        "other": 50}


def _tiny(files, seed=5):
    c = cells.from_files("tiny", *files, seed)
    c.config["graph"].update(counts=TINY, knows_mean_deg=5)
    c.traffic["distinct_calls"] = 1
    c.traffic["paths"]["paths_per_template"] = {
        "IS2": 50, "IS3": 40, "IS5": 6, "IS6": 6, "IS7": 4}
    c.data = gen_data.build(c.config, c.seed)
    return c


def _correct(c, numbers):
    checks = report.checks(numbers, c.limits)
    return all(v["value"] is not None and v["value"] <= v["limit"]
               for v in checks.values())


def _judge(c, drv, state):
    recs = drv.window(c, state, 0.05, _span)
    assert recs
    return _correct(c, drv.check(c, state, recs))


@pytest.fixture(scope="module", params=PROVISION, ids=lambda f: f[1])
def provision(request):
    c = _tiny(request.param)
    drv = cells.driver(c)
    return c, drv, drv.setup(c)


def test_provision_run_prints_a_correct_result(provision, monkeypatch):
    import bench.run as run

    c, drv, _ = provision
    c.end_to_end = E2E
    monkeypatch.setattr(report, "peaks",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.run(c, drv, 0.05, False, jax.devices()[:1]) == 0
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {m["name"] for m in c.end_to_end}


def test_provision_window_is_correct(provision):
    c, drv, state = provision
    assert _judge(c, drv, state)


def test_provision_control_is_not_correct(provision):
    c, drv, state = provision
    numbers = drv.control(c, state)
    assert numbers["paths_over_t"] > 0
    assert not _correct(c, numbers)


@pytest.mark.parametrize("fault", sorted(faults.PROVISION))
def test_provision_faults_are_not_correct(provision, fault):
    c, drv, state = provision
    with faults.planted(fault):
        assert not _judge(c, drv, state)
