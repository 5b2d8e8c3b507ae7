#!/usr/bin/env python3
"""Readings that the correctness limits are set from (not part of a run).

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 [--seconds 2]

A workload that ``BENCHMARK.json`` does not hold is named
``<config>.<traffic>`` and built from those files.

In one process, for each seed: the program's readings against the plain
reference (the lower readings), the control's (the reference in the
precision below the configuration's, put in the program's place: the
upper readings), and whatever faults the cell's driver plants. One JSON
line per seed on standard output. Refuses anything but a TPU, as a run
does.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated offered rates: latency at each "
                         "(serving cells; first seed only)")
    ap.add_argument("--chips", type=int, default=1,
                    help="chips of a cell named <config>.<traffic> that "
                         "BENCHMARK.json does not hold")
    ap.add_argument("--cpu", action="store_true",
                    help="skip the look for a chip (rehearsal only)")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    spec = R.load_json(R.ROOT / "BENCHMARK.json")
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(R.CACHE_DIR)
    sys.path.insert(0, str(R.ROOT / "src"))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    if args.workload not in {w["name"] for w in spec["workloads"]}:
        # a cell that BENCHMARK.json does not hold: <config>.<traffic>
        config, traffic = args.workload.split(".", 1)
        spec["workloads"].append({"name": args.workload, "config": config,
                                  "traffic": traffic, "chips": args.chips})

    def make_ctx(seed):
        ctx = R.Ctx(spec, args.workload, seed, args.seconds, False)
        R.check_device(ctx, require_tpu=not args.cpu)
        return ctx

    try:
        first = make_ctx(seeds[0])
    except R.Refused as e:
        print(f"calibrate: refused: {e}", file=sys.stderr)
        return 2
    driver = R.load_module(R.BENCH / "drivers" / f"{first.traffic['driver']}.py",
                           "bench_driver_" + first.traffic["driver"])
    rows = driver.calibrate(make_ctx, seeds, args.seconds, sys.stderr) \
        if args.sweep is None else driver.sweep(
            make_ctx, seeds[0], [float(r) for r in args.sweep.split(",")],
            args.seconds, sys.stderr)
    for row in rows:
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
