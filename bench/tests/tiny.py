"""Tiny stand-ins of the benchmark's cells, for tests on the CPU.

Each keeps the real cell's name (so its limits in ``bench/limits/`` apply)
and driver, with a configuration and traffic small enough for a test.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
TESTS = BENCH / "tests"


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def harness():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(ROOT / "src"))
    return load(BENCH / "run.py", "bench_run")


def spec():
    """BENCHMARK.json with every configuration and traffic file swapped
    for its tiny stand-in under ``bench/tests``."""
    with open(ROOT / "BENCHMARK.json") as f:
        s = json.load(f)
    tiny_cfg = {"whisper-base": "bench/tests/whisper-smoke.json",
                "table1-n2048": "bench/tests/table1-tiny.json"}
    # the sharded window's cell is not in BENCHMARK.json yet; its tiny
    # stand-in keeps the sharded driver path under test
    s["configs"].append({"name": "whisper-base", "file": ""})
    s["workloads"].append({"name": "whisper-base.window-sharded",
                           "config": "whisper-base",
                           "traffic": "window-sharded", "chips": 4})
    for c in s["configs"]:
        c["file"] = tiny_cfg[c["name"]]
    for w in s["workloads"]:
        f = TESTS / f"{w['traffic']}-tiny.json"
        if f.exists():
            w["traffic_file"] = str(f.relative_to(ROOT))
    return s


def run(workload, seed, seconds=1.0, patch=None):
    R = harness()
    return R.run_cell(workload, seed, seconds, False, require_tpu=False,
                      spec=spec(), patch=patch, log=open(os.devnull, "w"))
