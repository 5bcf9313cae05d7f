"""The k-resilient cell and the home_first cell at a small size on the CPU.

The plain k-resilient reference (``bench.reference.resilient``) against
the program: its rotation is the program's failover sharding, the
program's k = 1 scheme keeps every path within ``t`` under each loss case
by the reference walk, and holds about as many replicas as the reference
greedy.  Each new cell's window is judged correct; its control and the
program with its timed path broken are not.
"""
import contextlib
import io
import json

import jax
import numpy as np
import pytest

from bench.gen import data as gen_data
from bench.harness import cell as cells
from bench.harness import faults, report
from bench.reference import resilient as ref

K1 = ("bench/configs/snb_sf1_k1.json", "provision_nc_k1")
HF = ("bench/configs/snb_sf1.json", "provision_hf")
POLICIES = ["home_first", "nearest_copy"]
SMALL = {"person": 300, "forum": 200, "post": 3000, "comment": 6000,
         "other": 50}
QUOTA = {"IS2": 40, "IS3": 40, "IS5": 8, "IS6": 8, "IS7": 6}


def _span(name):
    return contextlib.nullcontext()


def _small(files, seed=5, policy=None):
    c = cells.from_files("small", *files, seed)
    c.config["graph"].update(counts=SMALL, knows_mean_deg=5)
    c.traffic["paths"]["paths_per_template"] = dict(QUOTA)
    if policy is not None:
        c.traffic["policy"] = policy
    c.data = gen_data.build(c.config, c.seed)
    return c


def _correct(c, numbers):
    checks = report.checks(numbers, c.limits)
    return all(v["value"] is not None and v["value"] <= v["limit"]
               for v in checks.values())


@pytest.mark.parametrize("S", [5, 6])
def test_reference_rotation_is_the_programs_failover(S):
    from repro.engine.resilience import KResilient, failover_shard

    shard = np.random.default_rng(S).integers(0, S, 500).astype(np.int32)
    for k in (1, 2):
        cases = ref.loss_cases(S, k)
        prog = KResilient(k).loss_cases(S)
        assert [c.tolist() for c in cases] == [c.tolist() for c in prog]
        for lost in cases:
            assert np.array_equal(ref.failover_homes(shard, lost, S),
                                  failover_shard(shard, lost, S))


def test_loss_view_strands_a_walk_on_an_object_with_no_copy():
    # object 1 held only on server 1; lose server 1: a walk from object 0
    # hops to it (remote), then object 2, held where the walk started,
    # is remote too (the walk stands on no server)
    mask = np.zeros((3, 3), bool)
    mask[[0, 1, 2], [0, 1, 0]] = True
    shard = np.asarray([0, 1, 0])
    objects, lengths = np.asarray([[0, 1, 2]]), np.asarray([3])
    for policy in POLICIES:
        assert ref.loss_latencies(objects, lengths, mask, shard, policy,
                                  np.asarray([1]))[0] == 2


@pytest.fixture(scope="module", params=POLICIES)
def k1_small(request):
    c = _small(K1, policy=request.param)
    drv = cells.driver(c)
    return c, drv, drv.setup(c)


def test_program_keeps_every_path_within_t_under_each_loss(k1_small):
    c, drv, state = k1_small
    (rec,) = drv.once(c, state, _span)
    assert not rec["failed"] and rec["stats"].resilient_violations == 0
    o, ln, _ = state["calls"][0]
    assert ref.over_t_under_loss(o, ln, rec["mask"], c.data["shard"], c.t,
                                 c.traffic["policy"], ref.loss_cases(6)) == 0


def test_program_replicas_within_the_limit_of_the_reference(k1_small):
    c, drv, state = k1_small
    (rec,) = drv.once(c, state, _span)
    m, n_ref = drv.reference_replicas(c, state["calls"][0], c.t)
    assert ref.over_t_under_loss(*state["calls"][0][:2], m, c.data["shard"],
                                 c.t, c.traffic["policy"],
                                 ref.loss_cases(6)) == 0
    excess = drv.scheme_numbers(c, state["calls"][0], rec["mask"],
                                n_ref)["replica_excess"]
    assert abs(excess) <= c.limits["replica_excess"]


@pytest.fixture(scope="module", params=[K1, HF], ids=lambda f: f[1])
def cell(request):
    c = _small(request.param)
    drv = cells.driver(c)
    return c, drv, drv.setup(c)


def test_cell_run_prints_a_correct_result(cell, monkeypatch):
    import bench.run as run

    c, drv, _ = cell
    c.end_to_end = [{"name": "provision_paths_per_s", "unit": "paths/s"},
                    {"name": "setup_s", "unit": "s"}]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.run(c, drv, 0.05, False, jax.devices()[:1]) == 0
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["checks"]) == set(c.limits)


def test_cell_control_is_not_correct(cell):
    c, drv, state = cell
    numbers = drv.control(c, state)
    moved = "paths_over_t_loss" if "paths_over_t_loss" in c.limits \
        else "paths_over_t"
    assert numbers[moved] > 0
    assert not _correct(c, numbers)


@pytest.mark.parametrize("fault", sorted(faults.PROVISION))
def test_cell_faults_are_not_correct(cell, fault):
    c, drv, state = cell
    with faults.planted(fault):
        recs = drv.window(c, state, 0.05, _span)
    assert recs and not _correct(c, drv.check(c, state, recs))


def test_cell_first_choice_reference_is_not_correct(cell):
    c, drv, state = cell
    arrays = state["calls"][0]
    o, ln, _ = arrays
    _, n_ref = drv.reference_replicas(c, arrays, c.t)
    m = drv.ref.provision(o, ln, c.data["shard"], c.data["n_servers"], c.t,
                          c.traffic["policy"], first=True)
    assert drv.scheme_numbers(c, arrays, m, n_ref)["replica_excess"] > \
        c.limits["replica_excess"]
