"""Run one cell of the benchmark on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the cell's data from the seed and warms every program its
traffic uses; the window then drives the program for ``--seconds``; a
plain reference then judges what the window produced.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics, read from the profiler trace of the
window and the program's counters), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number beside its
limit, also printed as the last lines of standard error.

Exits 1, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for.  The compile cache is ``$JAX_COMPILATION_CACHE_DIR``
when that is set, else ``bench/.jax_cache`` in this checkout.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _no_span(name):
    return contextlib.nullcontext()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.harness import cell as cells

    cell = cells.resolve(args.workload, args.seed)
    drv = cells.driver(cell)

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(BENCH, ".jax_cache"))
    # keep every program, however quick its compile, so that a second run
    # in this checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: needs {cell.chips} TPU chip(s), found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    used = devices[: cell.chips]
    return run(cell, drv, args.seconds, bool(args.trace), used)


def run(cell, drv, seconds: float, trace: bool, used) -> int:
    import jax

    from bench.gen import data as gen_data
    from bench.harness import report
    from repro import obs

    # the program's counters are per-layer instruments: on in traced runs
    # only, like the profiler; the compile counter counts in every run
    if trace:
        obs.enable()
    compiles = obs.install_compile_hook()

    t0 = time.perf_counter()
    cell.data = gen_data.build(cell.config, cell.seed)
    t_data = time.perf_counter() - t0
    state = drv.setup(cell)
    setup_s = time.perf_counter() - t0
    print(f"setup {setup_s:.3f} s (data {t_data:.3f} s)  objects "
          f"{cell.data['n_objects']}  relationships "
          f"{json.dumps(cell.data['relationships'])}", flush=True)

    counters0 = obs.REGISTRY.snapshot()
    c0 = compiles.value if compiles is not None else 0
    span = _no_span
    prof_dir = None
    if trace:
        prof_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # Python calls would swamp the host
        opts.host_tracer_level = 1     # the harness's spans
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(prof_dir, profiler_options=opts)
        span = jax.profiler.TraceAnnotation
    tw = time.perf_counter()
    recs = drv.window(cell, state, seconds, span)
    window_wall = time.perf_counter() - tw
    if trace:
        jax.profiler.stop_trace()
    in_window = (compiles.value - c0) if compiles is not None else None
    counters = report.counter_delta(counters0, obs.REGISTRY.snapshot())
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    summary = drv.summary(cell, state, recs)
    summary["device_kind"] = used[0].device_kind
    print(f"window {window_wall:.3f} s  operations {len(recs)}  compiles in "
          f"window {in_window}", flush=True)
    print("summary " + json.dumps(summary), flush=True)

    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": int(peak)}
    out = {"attempted": len(recs),
           "failed": sum(1 for r in recs if r["failed"])}
    if trace:
        from bench.harness import trace as tr

        tdata = tr.load(prof_dir)
        shutil.rmtree(prof_dir, ignore_errors=True)
        metrics, extra = report.per_layer(cell, tdata, counters, summary)
        device.update(extra["device"])
        out["breakdown"] = extra["breakdown"]
        print("trace layout " + json.dumps(extra["layout"]), flush=True)
    else:
        values = dict(drv.end_to_end(cell, recs), setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    # the reference runs once the window's device state is released: the
    # records hold host arrays only
    tc = time.perf_counter()
    numbers = drv.check(cell, state, recs)
    print(f"reference {time.perf_counter() - tc:.3f} s", flush=True)
    checks = report.checks(numbers, cell.limits)
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    result = {"correct": correct, **out, "metrics": metrics,
              "device": device}
    if "breakdown" in out:
        result["breakdown"] = result.pop("breakdown")
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
