"""``BENCHMARK.json`` resolves, by name, to the files under ``bench/``; a
cell, a traffic mix and a metric added as files alone are found."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench.harness import cell as cells

ROOT = cells.ROOT
SPEC = cells.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1] == "bench/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_arrows():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    names = [c["name"] for c in SPEC["configs"]] + [
        w["name"] for w in SPEC["workloads"]] + [
        m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_workload_resolves(w):
    c = cells.resolve(w["name"], 1)
    assert c.chips in (1, 4)
    assert os.path.exists(os.path.join(ROOT, "bench", "drivers",
                                       c.traffic["driver"] + ".py"))
    cells.driver(c)
    assert set(c.limits)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e, (m["name"], w["name"])
        assert hasattr(cells.metric_reader(m["name"]), "read")


def test_config_files_hold_what_they_are_run_with():
    for c in SPEC["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert {"graph", "servers", "sharding", "t", "max_len"} <= set(cfg)


def test_a_cell_added_from_files_alone_is_found(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".cache",
                                                  "__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    base = next(w for w in spec["workloads"]
                if w["traffic"].startswith("provision"))
    with open(cells.traffic_file(base["traffic"])) as f:
        mix = json.load(f)
    mix["paths"]["paths_per_template"] = {"IS2": 100}
    (root / "bench" / "traffic" / "is2_only.json").write_text(json.dumps(mix))
    (root / "bench" / "metrics" / "calls.count.py").write_text(
        "def read(ctx):\n    return float(ctx['summary']['calls'])\n")
    spec["workloads"].append({"name": "snb_sf1.is2_only", "config":
                              base["config"], "traffic": "is2_only",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "calls.count", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "driver", "moves": spec["end_to_end"][0]["name"],
                              "workloads": ["snb_sf1.is2_only"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    c = cells.resolve("snb_sf1.is2_only", 5, root=str(root))
    assert c.traffic["paths"]["paths_per_template"] == {"IS2": 100}
    assert [m["name"] for m in c.per_layer][-1] == "calls.count"
    reader = cells.metric_reader("calls.count", root=str(root))
    assert reader.read({"summary": {"calls": 3}}) == 3.0
    assert cells.driver(c, root=str(root)).SPAN


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    w = SPEC["workloads"][0]["name"]
    p = _run(["bench/run.py", "--workload", w, "--seed", "3",
              "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "TPU" in p.stderr


def test_in_a_directory_of_only_the_benchmark_it_exits_nonzero(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".cache",
                                                  "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    w = SPEC["workloads"][0]["name"]
    p = _run(["bench/run.py", "--workload", w, "--seed", "3",
              "--seconds", "1", "--trace", "0"], str(tmp_path))
    assert p.returncode != 0
    assert "metrics" not in p.stdout
