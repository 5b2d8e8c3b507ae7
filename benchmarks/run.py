"""Benchmark orchestrator. One section per paper table/figure plus the
framework-level harnesses. Prints ``name,us_per_call,derived`` CSV; with
``--json`` additionally writes machine-readable ``BENCH_solvers.json`` and
``BENCH_ngd.json`` (one row per measurement: name, us_per_call, derived,
config, peak_mem_bytes) so the perf trajectory is tracked across PRs.

``--tiny`` shrinks every shape to CI-smoke size (seconds, not minutes);
``--full`` runs the exact paper grid. ``--fleet`` adds the multi-process
fleet section, which spawns worker subprocesses: it is refused on a TPU
host, where this process holds the chip and the workers could not open it.
"""
from __future__ import annotations

import json
import re
import sys

_MEM_ROW = re.compile(r"(\d+)\s*B?\)?$")


def _collector(config, peaks=None):
    """(rows, emit): emit prints the CSV line and parses it into a row.

    ``peaks``: dtype -> compiled peak bytes of the section's request path
    (``benchmarks/memutil``) — the default ``peak_mem_bytes`` for rows
    that don't state their own memory number."""
    rows = []

    def emit(line):
        print(line)
        parts = line.split(",", 2)
        name = parts[0]
        us = parts[1] if len(parts) > 1 else ""
        derived = parts[2] if len(parts) > 2 else ""
        peak = None
        if "mem" in name:
            m = _MEM_ROW.search(derived.strip())
            if m:
                peak = int(m.group(1))
        if peak is None and peaks:
            from benchmarks import memutil
            peak = memutil.peak_for_row(name, peaks)
        rows.append({"name": name,
                     "us_per_call": float(us) if us else None,
                     "derived": derived,
                     "config": config,
                     "peak_mem_bytes": peak})
    return rows, emit


def _write_json(path, rows):
    with open(path, "w") as fh:
        json.dump(rows, fh, indent=1)
    print(f"# wrote {path} ({len(rows)} rows)")


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    full = "--full" in argv
    tiny = "--tiny" in argv
    as_json = "--json" in argv
    fleet = "--fleet" in argv
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if fleet:
        import jax

        from repro.fleet import refuse_on_tpu_parent
        jax.devices()
        refuse_on_tpu_parent("benchmarks/run.py --fleet")
    print("name,us_per_call,derived")

    solver_rows = []
    ngd_rows = []
    serve_rows = []

    from benchmarks import table1_solvers
    # tiny sweeps are disjoint so BENCH_solvers.json row names stay unique
    n_sweep = [(32, 2_000), (64, 2_000)] if tiny else None
    m_sweep = [(48, 1_000), (48, 3_000)] if tiny else None
    rows, emit = _collector({"section": "table1", "full": full,
                             "tiny": tiny})
    table1_solvers.run(full=full, emit=emit, n_sweep=n_sweep, m_sweep=m_sweep)
    solver_rows += rows

    from benchmarks import kernels
    rows, emit = _collector({"section": "kernels", "tiny": tiny})
    kernels.run(emit=emit, shapes=((64, 2_000),) if tiny
                else ((512, 50_000),))
    solver_rows += rows

    from benchmarks import ngd_step
    bs = dict(batch=4, seq=16) if tiny else dict(batch=16, seq=64)
    rows, emit = _collector({"section": "ngd_step", **bs})
    ngd_step.run(emit=emit, **bs)
    ngd_step.run_blocked(emit=emit, assert_below=not tiny, **bs)
    ngd_rows += rows

    from benchmarks import amortized
    am = dict(n=64, m=2_000, k=8) if tiny else dict(n=256, m=25_000, k=16)
    rows, emit = _collector({"section": "amortized", **am})
    # tiny shapes sit at the dispatch-overhead floor where the O(n²k)-vs-
    # O(n²m) separation vanishes; the speedup gate runs at the real shape.
    amortized.run(emit=emit, assert_speedup=not tiny, **am)
    if not tiny:
        amortized.run_trainer(emit=emit)
    ngd_rows += rows

    from benchmarks import memutil, serve
    sv = dict(n=64, m=2_000, requests=24, k=4) if tiny \
        else dict(n=512, m=25_000, requests=48, k=8)
    peaks = {"fp32": memutil.serve_request_peak_bytes(**sv),
             "bf16": memutil.serve_request_peak_bytes(
                 window_dtype="bfloat16", **sv)}
    rows, emit = _collector({"section": "serve", **sv}, peaks=peaks)
    # tiny shapes sit at the dispatch floor (see benchmarks/serve.py);
    # the >=5x request-path gate runs at the real m >> n shape only.
    serve.run(emit=emit, assert_speedup=not tiny, **sv)
    # fused-vs-compositional + bf16-window pair: the req/s gate is
    # TPU-only (CPU dispatches the same jnp reference both ways); the
    # bf16 byte-ratio and 5e-3 equivalence asserts run at every shape.
    serve.run_fused_dtypes(emit=emit, assert_fused=not tiny, **sv)
    # observability cost ceiling: metrics+tracing <= 5% req/s on the
    # coalesced path, gated at the real shape (tiny rows report-only).
    serve.run_obs_overhead(emit=emit, assert_overhead=not tiny, **sv)
    # numerical-health observatory ceiling: margins + cadenced
    # condest/residual audit + rule engine keep >= 95% of audit-off
    # req/s, gated at the real shape (tiny rows report-only).
    serve.run_audit_overhead(emit=emit, assert_overhead=not tiny, **sv)
    serve_rows += rows

    from benchmarks import serve_dist
    rows, emit = _collector({"section": "serve_dist", **sv}, peaks=peaks)
    # same dispatch-floor policy: the async >= 1x eager req/s gate runs
    # at the real shape only; tiny rows are still trend-guarded.
    serve_dist.run(emit=emit, assert_ratio=not tiny, **sv)
    serve_rows += rows

    if fleet:
        from benchmarks import serve_fleet
        fv = dict(n=64, m=2_000, requests=16, k=4) if tiny \
            else dict(n=512, m=25_000, requests=48, k=8)
        rows, emit = _collector({"section": "serve_fleet", **fv},
                                peaks=peaks)
        # subprocess workers + real sockets: the >=1.5x 2-worker scaling
        # gate runs at the real shape on >=4-core hosts; reconciled-
        # agreement asserts run at every shape, and all rows are
        # trend-guarded.
        serve_fleet.run(emit=emit, assert_ratio=not tiny, **fv)
        serve_rows += rows

    from benchmarks import serve_tenants
    tv = dict(n=64, m=2_000, rank=4, tenants=96, resident_cap=16,
              requests=8) if tiny \
        else dict(n=512, m=25_000, rank=8, tenants=1_000, resident_cap=64,
                  requests=24)
    rows, emit = _collector({"section": "serve_tenants", **tv})
    # the 5e-3 private-window equivalence, the O(n·r) resident-bytes
    # bound, and the bit-identical evict->activate round trip assert at
    # every shape; latency rows are trend-guarded
    serve_tenants.run(emit=emit, **tv)
    serve_rows += rows

    from benchmarks import roofline
    rows, emit = _collector({"section": "roofline"})
    roofline.run(emit=emit)
    solver_rows += rows

    if as_json:
        _write_json("BENCH_solvers.json", solver_rows)
        _write_json("BENCH_ngd.json", ngd_rows)
        _write_json("BENCH_serve.json", serve_rows)


if __name__ == "__main__":
    main()
