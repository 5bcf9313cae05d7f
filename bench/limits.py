"""Read, on the chip at a cell's own size, what its limits are set from.

    python bench/limits.py --workload <name> --seeds 11,12,13

For each seed, in one process: the cell's data and set-up, then each
compared number as read on
  sound     what the program produced (the lower readings),
  control   the contract's control (see the driver's ``control``),
  faults    the program with a fault planted in its timed path
            (``bench.harness.faults``), the reference put in the
            program's place with its UPDATE
            taking the first choice instead of the cheapest, and the
            program with its same-policy prune switched off
            (``policy_prune=False``, nearest_copy cells).
One JSON line per seed and reading.  Exits 1 without a TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _span(name):
    return contextlib.nullcontext()


def provision_readings(cell, drv, state) -> dict:
    from bench.harness import faults

    out = {"sound": drv.check(cell, state, drv.once(cell, state, _span)),
           "control": drv.control(cell, state)}
    for name in sorted(faults.PROVISION):
        with faults.planted(name):
            out[name] = drv.check(cell, state,
                                  drv.once(cell, state, _span))
    worst: dict = {}
    for arrays in state["calls"]:
        _, n_ref = drv.reference_replicas(cell, arrays, cell.t)
        o, ln, _ = arrays
        m = drv.ref.provision(o, ln, cell.data["shard"],
                              cell.data["n_servers"], cell.t,
                              cell.traffic["policy"], first=True)
        for k, v in drv.scheme_numbers(cell, arrays, m, n_ref).items():
            worst[k] = max(worst.get(k, v), v)
    out["first_choice"] = worst
    if cell.traffic["policy"] != "home_first":
        import repro.core.greedy as greedy

        orig = greedy.replicate_workload

        def no_prune(*a, **kw):
            return orig(*a, policy_prune=False, **kw)

        greedy.replicate_workload = no_prune
        try:
            out["prune_off"] = drv.check(cell, state,
                                         drv.once(cell, state, _span))
        finally:
            greedy.replicate_workload = orig
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench.gen import data as gen_data
    from bench.harness import cell as cells

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(BENCH, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("limits: no TPU", file=sys.stderr)
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cell = cells.resolve(args.workload, seed)
        drv = cells.driver(cell)
        cell.data = gen_data.build(cell.config, seed)
        state = drv.setup(cell)
        read = provision_readings(cell, drv, state)
        for k, v in read.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "reading": k, "numbers": v}), flush=True)
        print(f"seed {seed} {time.perf_counter() - t0:.1f} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
