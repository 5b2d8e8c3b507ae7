#!/usr/bin/env python3
"""One benchmark run of one cell on the accelerator this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is data found by name: ``BENCHMARK.json``
names the cell's configuration and traffic mix; the configuration is
``bench/configs/<config>.json`` with its plain reference beside it
(``<config>.py``); the traffic mix is ``bench/traffic/<traffic>.json``,
whose ``driver`` names the general driver in ``bench/drivers/`` that
builds the system under test and offers it that traffic; each limit of
the correctness check is in ``bench/limits/<workload>.json``; each
per-layer metric is a reader ``bench/metrics/<metric>.py``.

A run: refuse anything but a TPU with the peak table's ``device_kind`` and
enough chips; set up (weights, windows and requests made on the device
from ``--seed``; every program the window runs is compiled and warmed);
measure for ``--seconds``; read the device's peak memory; free the
program's state; compare what the timed path produced with the plain
reference. With ``--trace 1`` the window runs under the JAX profiler and
the per-layer metrics are read from its trace. The last line of standard
output is one JSON object; the numbers compared, each beside its limit,
end standard error and the result line.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import sys
import time
import traceback
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
# fixed, inside the checkout: the path is part of the cache key
CACHE_DIR = ROOT / "artifacts" / "bench_jax_cache"
TRACE_DIR = ROOT / "artifacts" / "bench_trace"


class Refused(Exception):
    """The run cannot measure here (wrong device, too few chips)."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


class Ctx:
    """What a driver gets: the cell's data, the seed and the devices."""

    def __init__(self, spec: dict, workload: str, seed: int, seconds: float,
                 trace: bool):
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; known: "
                             f"{sorted(cells)}")
        self.spec = spec
        self.cell = cells[workload]
        self.workload = workload
        cfg_entry = {c["name"]: c for c in spec["configs"]}[self.cell["config"]]
        cfg_path = ROOT / cfg_entry["file"]
        self.config = load_json(cfg_path)
        ref_path = ROOT / self.config["reference"] \
            if "reference" in self.config else cfg_path.with_suffix(".py")
        self.ref = load_module(ref_path, "bench_ref_" + ref_path.stem
                               .replace("-", "_").replace(".", "_"))
        self.solve_ref = self.module("solve_ref")
        self.traffic = load_json(
            ROOT / self.cell["traffic_file"] if "traffic_file" in self.cell
            else BENCH / "traffic" / f"{self.cell['traffic']}.json")
        limits = BENCH / "limits" / f"{workload}.json"
        self.limits = load_json(limits)["limits"] if limits.exists() else {}
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.chips = int(self.cell["chips"])
        self.devices = None
        self.peaks = None
        self.device_kind = None

    def module(self, name: str):
        """A module of the yardstick, ``bench/<name>.py``, loaded once."""
        mods = self.__dict__.setdefault("_modules", {})
        if name not in mods:
            mods[name] = load_module(BENCH / f"{name}.py", "bench_" + name)
        return mods[name]

    def key(self, *path: int):
        """A JAX key from the full seed (every bit of it) and a path."""
        import jax
        import jax.numpy as jnp
        s = self.seed & (2 ** 64 - 1)
        k = jax.random.wrap_key_data(
            jnp.asarray([s >> 32, s & 0xFFFFFFFF], jnp.uint32))
        for p in path:
            k = jax.random.fold_in(k, p)
        return k


def check_device(ctx: Ctx, *, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        raise Refused(f"no TPU: JAX platform {d0.platform!r}")
    if len(devs) < ctx.chips:
        raise Refused(f"{len(devs)} device(s), the cell asks for {ctx.chips}")
    peaks = load_json(BENCH / "peaks.json")["kinds"]
    if require_tpu and d0.device_kind not in peaks:
        raise Refused(f"device_kind {d0.device_kind!r} is not in "
                      "bench/peaks.json")
    ctx.devices = devs[:ctx.chips]
    ctx.device_kind = d0.device_kind
    ctx.peaks = peaks.get(d0.device_kind)
    return devs


class CompileCounter:
    """Counts XLA backend compilations (JAX's monitoring events)."""

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.n += 1
            self.seconds += duration


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def metric_names(spec: dict, workload: str, kind: str, reported_e2e=()):
    out = []
    for m in spec[kind]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in reported_e2e:
            out.append(m)
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, spec: dict | None = None,
             patch=None, log=sys.stderr, t0: float = T_START) -> dict:
    """Set up, measure, check; returns the result object (not printed).

    ``patch(ctx, cell)`` runs after set-up and may break the timed path
    (the fault tests); ``require_tpu=False`` skips the look for a chip.
    ``setup_s`` counts from ``t0``, the process start by default.
    """
    spec = spec if spec is not None else load_json(ROOT / "BENCHMARK.json")
    ctx = Ctx(spec, workload, seed, seconds, trace)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    check_device(ctx, require_tpu=require_tpu)
    compiles = CompileCounter()
    driver = load_module(BENCH / "drivers" / f"{ctx.traffic['driver']}.py",
                         "bench_driver_" + ctx.traffic["driver"])

    cell = driver.setup(ctx)
    if patch is not None:
        patch(ctx, cell)
    # what set-up allocated moves out of the collector's reach, so that a
    # collection inside the window scans only the window's own objects
    gc.collect()
    gc.freeze()
    n0, c0 = compiles.n, compiles.seconds
    t_window = time.perf_counter()
    setup_s = t_window - t0
    tracer = None
    if trace:
        tracer = ctx.module("trace").Capture(TRACE_DIR / workload)
        tracer.start()
    try:
        win = cell.window(seconds)
    finally:
        if tracer is not None:
            tracer.stop()
        gc.unfreeze()
    in_window = (compiles.n - n0, compiles.seconds - c0)
    mem = memory_peak(ctx.devices)
    print(f"window: {win['attempted']} attempted, {win['failed']} failed; "
          f"compilations inside the window: {in_window[0]} "
          f"({in_window[1]:.3f} s)", file=log, flush=True)
    for line in win.get("notes", ()):
        print(line, file=log, flush=True)
    reduced = tracer.reduce(ctx, cell) if tracer is not None else None
    cell.release()
    checks = cell.check()

    correct = bool(checks) and all(
        v == v and v <= lim for v, lim in checks.values())
    e2e = metric_names(spec, workload, "end_to_end")
    e2e_vals = dict(win["metrics"], setup_s=setup_s)
    metrics = {}
    if not trace:
        for m in e2e:
            if m["name"] in e2e_vals:
                metrics[m["name"]] = {"value": e2e_vals[m["name"]],
                                      "unit": m["unit"]}
    else:
        reported = [m["name"] for m in e2e]
        for m in metric_names(spec, workload, "per_layer", reported):
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 "bench_metric_" + m["name"].replace(".", "_"))
            val = reader.read(reduced, cell, ctx)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    devs = ctx.devices
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": int(win["attempted"]),
              "failed": int(win["failed"]), "metrics": metrics,
              "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in checks.items()}
    for name, (v, lim) in checks.items():
        print(f"check {name}: {v!r} (limit {lim!r}) "
              f"{'ok' if v == v and v <= lim else 'FAILED'}", file=log,
              flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
