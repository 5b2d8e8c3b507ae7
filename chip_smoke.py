#!/usr/bin/env python3
"""Drive the main path once on a TPU, through the entry points a user calls.

    python3 chip_smoke.py                # one chip: phases (a)-(e)
    python3 chip_smoke.py --four-chips   # four chips: the sharded window only

One chip, in one process and in this order:

  (a) device check — exits non-zero unless JAX's first device is a TPU;
  (b) Algorithm 1 (``repro.core.chol_solve``) at a Table-1 shape, n=2048,
      m=100,000, fp32: relative residual of (SᵀS+λI)x = v and agreement
      with the ``eigh`` solver;
  (c) every main-path Pallas kernel at real widths through
      ``repro.kernels.ops`` with ``mode=None``: the compiled program must
      hold a ``tpu_custom_call`` (no interpret mode, no reference
      fallback) and the result must match ``repro.kernels.ref`` within the
      kernel tests' tolerances;
  (d) ``train_main``: whisper-base at full width, NGD, 3 steps at n=8 —
      finite losses and no supervisor restart;
  (e) ``serve_main``: whisper-base at full width, 6 requests over 2
      tenants, whose folds go through the compiled ``cholupdate`` kernel.

``--four-chips`` runs only the sharded window: the ``AsyncSolveServer`` on
a 1d ``DistSpec`` over a (4,) mesh against the replicated single-device
``SolveServer`` on the same trace (agreement within 5e-3), with each
device holding a quarter of the window.

Each phase prints its wall time, compile time and the device's peak
memory so far. The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``,
printed only when every phase passed. Checkpoints go to fixed directories
under ``artifacts/`` that each run wipes first.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

TRAIN_CKPT = ROOT / "artifacts" / "chip_smoke" / "train_ckpt"
SERVE_CKPT = ROOT / "artifacts" / "chip_smoke" / "serve_ckpt"

# (b) the paper's Table-1 shape
PAPER_N, PAPER_M = 2048, 100_000
# (c) the serve window's real shape: n samples, m parameters, k requests
KN, KM, KK = 512, 25_000, 8
# (d)/(e) whisper-base at full width: n samples per step / window, decoder
# tokens per sample (the v5e compile of the n=8, seq=64 NGD step needs
# 5.6 GiB; the decoder is capped at 448 positions)
MODEL_N, MODEL_SEQ = 8, 64
# --four-chips: a window whose quarter is unmistakable in bytes_in_use
DIST_N, DIST_M = 512, 2_048_000


class Phases:
    """Runs each phase, times it, and records pass/fail without stopping."""

    def __init__(self, jax):
        self.jax = jax
        self.failed = []
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.compile_s += duration

    def run(self, name, fn):
        print(f"== phase {name}", flush=True)
        c0, t0 = self.compile_s, time.perf_counter()
        try:
            fn()
            status = "ok"
        except Exception:
            traceback.print_exc()
            self.failed.append(name)
            status = "FAILED"
        stats = self.jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        print(f"== phase {name}: {status} wall {time.perf_counter() - t0:.1f} s"
              f" compile {self.compile_s - c0:.1f} s"
              f" device0 peak_bytes_in_use {peak}", flush=True)


def check(ok, detail) -> None:
    """A result check that also holds under ``python -O``."""
    if not ok:
        raise AssertionError(detail)


def _rel(a, b):
    import numpy as np
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-6))


def phase_paper_solve(n=PAPER_N, m=PAPER_M):
    import jax
    import jax.numpy as jnp
    from repro.configs.paper import DAMPING
    from repro.core import chol_solve, eigh_solve, residual

    k1, k2 = jax.random.split(jax.random.key(0))
    S = jax.random.normal(k1, (n, m), jnp.float32) / jnp.sqrt(m)
    v = jax.random.normal(k2, (m,), jnp.float32)
    chol = jax.jit(lambda S, v: chol_solve(S, v, DAMPING))
    eigh = jax.jit(lambda S, v: eigh_solve(S, v, DAMPING))
    x = chol(S, v).block_until_ready()
    t0 = time.perf_counter()
    x = chol(S, v).block_until_ready()
    print(f"chol_solve n={n} m={m}: {(time.perf_counter() - t0) * 1e3:.2f} ms"
          " (second call)")
    xe = eigh(S, v)
    with jax.default_matmul_precision("highest"):
        res = float(residual(S, v, x, DAMPING))
        # the damped system's spectral condition number: ‖S‖_F² ≈ n would
        # put the bound above 1, where even x = 0 passes
        top = float(jnp.linalg.eigvalsh(S @ S.T)[-1])
    kappa = (top + DAMPING) / DAMPING
    bound = max(1e-3, 3e-6 * kappa)
    agree = float(jnp.linalg.norm(x - xe) / jnp.linalg.norm(xe))
    print(f"relative residual {res:.3e} (bound {bound:.3e}, spectral kappa"
          f" {kappa:.1f}); chol vs eigh {agree:.3e} (bound 5e-3)")
    check(res < bound, res)
    check(agree < 5e-3, agree)


def _kernel_cases(n, m, k):
    """(name, ops entry, ref entry, args, tolerance) at real widths."""
    import functools

    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    keys = iter(jax.random.split(jax.random.key(1), 16))

    def normal(shape, scale=1.0, dtype=jnp.float32):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def window(dtype, lam=0.2):
        S = normal((n, m), 1 / m ** 0.5, dtype)
        S32 = S.astype(jnp.float32)
        W = jnp.matmul(S32, S32.T, precision="highest")
        return S, jnp.linalg.cholesky(W + lam * jnp.eye(n))

    def spd(size):
        A = normal((size, size))
        return jnp.matmul(A, A.T, precision="highest") + size * jnp.eye(size)

    def op(fn, *a, **kw):
        return functools.partial(fn, *a, **kw)

    cases = []
    for dt, tag in ((jnp.float32, "f32"), (jnp.bfloat16, "bf16")):
        S, L = window(dt)
        V = normal((m, k))
        cases.append((f"serve_solve[{tag}]",
                      lambda S, L, V: ops.serve_solve(S, L, V, 0.2, mode=None),
                      lambda S, L, V: ref.serve_solve_ref(S, L, V, 0.2),
                      (S, L, V), 5e-5))
    S, V, w = normal((n, m)), normal((m, k)), normal((n, k))
    cases.append(("sv_cross", op(ops.sv_cross, mode=None), ref.sv_cross_ref,
                  (S, V), 5e-6))
    cases.append(("serve_apply",
                  lambda S, w, V: ops.serve_apply(S, w, V, 0.37, mode=None),
                  lambda S, w, V: ref.serve_apply_ref(S, w, V, 0.37),
                  (S, w, V), 5e-6))
    cases.append(("fold_cols", op(ops.fold_cols, mode=None),
                  ref.fold_cols_ref, (S, normal((k, m))), 5e-6))
    X = normal((n, k))
    for sign, tag in ((1, "update"), (-1, "downdate")):
        W = spd(n) + (X @ X.T if sign < 0 else 0.0)
        L0 = jnp.linalg.cholesky(W)
        cases.append((f"cholupdate[{tag}]",
                      op(ops.cholupdate, sign=sign, mode=None),
                      op(ref.cholupdate_ref, sign=sign), (L0, X), 1e-5))
    for size in (n, 1024):
        cases.append((f"cholesky[n={size}]", op(ops.cholesky, mode=None),
                      ref.cholesky_ref, (spd(size),), 1e-5))
    v = normal((m,))
    cases.append(("gram_sv", op(ops.gram_sv, mode=None), ref.gram_sv_ref,
                  (S, v), 5e-6))
    cases.append(("ngd_apply",
                  lambda S, w, v: ops.ngd_apply(S, w, v, 0.37, mode=None),
                  lambda S, w, v: ref.ngd_apply_ref(S, w, v, 0.37),
                  (S, w[:, 0], v), 5e-6))
    return cases


def phase_kernels(n=KN, m=KM, k=KK):
    import jax

    bad = []
    for name, fn, ref_fn, args, tol in _kernel_cases(n, m, k):
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        compile_ms = (time.perf_counter() - t0) * 1e3
        kernel = "tpu_custom_call" in compiled.as_text()
        out = jax.block_until_ready(compiled(*args))
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        run_ms = (time.perf_counter() - t0) * 1e3
        want = jax.jit(ref_fn)(*args)
        outs = out if isinstance(out, tuple) else (out,)
        wants = want if isinstance(want, tuple) else (want,)
        err = max(_rel(o, w) for o, w in zip(outs, wants))
        ok = kernel and err < tol
        print(f"kernel {name}: tpu_custom_call={kernel} rel_err {err:.3e}"
              f" (tol {tol:g}) compile {compile_ms:.0f} ms"
              f" run {run_ms:.3f} ms {'ok' if ok else 'FAILED'}", flush=True)
        if not ok:
            bad.append(name)
    check(not bad, f"kernels failed: {bad}")


def _wipe(path):
    shutil.rmtree(path, ignore_errors=True)
    path.parent.mkdir(parents=True, exist_ok=True)


def phase_train(n=MODEL_N, seq=MODEL_SEQ, extra=()):
    import math

    from repro.launch.trainer import train_main

    _wipe(TRAIN_CKPT)
    losses, report = train_main(
        ["--arch", "whisper-base", "--optimizer", "ngd", "--steps", "3",
         "--batch", str(n), "--seq", str(seq), "--log-every", "1",
         "--ckpt-dir", str(TRAIN_CKPT), *extra])
    check(len(losses) == 3 and all(math.isfinite(x) for x in losses), losses)
    check(report["restarts"] == 0 and report["completed"], report)


def phase_serve(n=MODEL_N, seq=MODEL_SEQ, extra=("--full",)):
    import math

    from repro.kernels import ops
    from repro.serve.main import serve_main

    calls = []
    kernel = ops.cholupdate_pallas

    def counted(*a, **kw):
        calls.append(kw.get("sign"))
        return kernel(*a, **kw)

    _wipe(SERVE_CKPT)
    ops.cholupdate_pallas = counted
    try:
        server, losses = serve_main(
            ["--arch", "whisper-base", *extra, "--requests", "6",
             "--window", str(n), "--seq", str(seq), "--tenants", "2",
             "--decode-tokens", "0", "--ckpt-dir", str(SERVE_CKPT)])
    finally:
        ops.cholupdate_pallas = kernel
    served = server.metrics.summary()["served"]
    builds = server.tenants.packing_stats()["materializations"]
    print(f"served {served} requests; {builds} tenant factor builds; "
          f"cholupdate kernel dispatches {len(calls)} (signs {sorted(set(calls))})")
    check(served == 6 and len(losses) == 6, (served, losses))
    check(all(math.isfinite(x) for x in losses), losses)
    check(builds > 0 and calls, (builds, calls))


def phase_four_chips(n=DIST_N, m=DIST_M):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist import AsyncSolveServer, DistSpec, init_sharded_serve_state
    from repro.launch.mesh import make_mesh
    from repro.serve import (OnlineAdaptation, SolveServer,
                             TokenBudgetBatcher, init_serve_state)

    devices = jax.devices()[:4]
    check(len(devices) == 4, devices)
    mesh = make_mesh((4,), ("model",))
    keys = jax.random.split(jax.random.key(2), 12)
    window_bytes = n * m * 4
    before = [d.memory_stats()["bytes_in_use"] for d in devices]
    # the window is drawn already sharded: no device ever holds all of it
    S = jax.jit(lambda key: jax.random.normal(key, (n, m)) / np.sqrt(m),
                out_shardings=NamedSharding(mesh, P(None, "model")))(keys[0])
    state = init_sharded_serve_state(S, 0.1, spec=DistSpec(mesh, "1d"))
    del S
    gc.collect()
    held = [d.memory_stats()["bytes_in_use"] - b
            for d, b in zip(devices, before)]
    shards = state.state.S.addressable_shards
    print(f"window {window_bytes} B; shards "
          f"{[(str(s.device), s.data.shape, s.data.nbytes) for s in shards]};"
          f" bytes_in_use added per device {held}")
    check(len({s.device for s in shards}) == 4, shards)
    check(all(s.data.nbytes == window_bytes // 4 for s in shards), shards)
    check(all(window_bytes // 4 <= h < window_bytes // 2 for h in held), held)

    vs = [jax.random.normal(keys[1 + i], (m,)) for i in range(8)]
    lams = [None, 0.3, None, None, 0.05, None, 0.3, None]
    rows = jax.random.normal(keys[9], (3, m)) / np.sqrt(m)

    def drive(server):
        sub = {}
        for i, (v, lam) in enumerate(zip(vs, lams)):
            sub[server.submit(v, damping=lam,
                              rows=rows if i in (3, 5) else None)] = i
        return {sub[r.uid]: np.asarray(r.x) for r in server.flush()}

    def adapt():
        return OnlineAdaptation(refresh_every=10 ** 6, drift_frac=None)

    srv = AsyncSolveServer(state, batcher=TokenBudgetBatcher(max_requests=2),
                           adaptation=adapt())
    try:
        got = drive(srv)
    finally:
        srv.shutdown()
    S0 = jax.device_put(state.state.S, devices[0])
    del srv, state
    ref = drive(SolveServer(init_serve_state(S0, 0.1),
                            batcher=TokenBudgetBatcher(max_requests=2),
                            adaptation=adapt()))
    rel = max(float(np.linalg.norm(got[i] - ref[i]) / np.linalg.norm(ref[i]))
              for i in ref)
    print(f"sharded 1d x4 vs replicated single-device: max rel err {rel:.3e}"
          f" over {len(ref)} requests (bound 5e-3)")
    check(rel < 5e-3, rel)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded window on four chips")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}")
    import jax

    devs = jax.devices()
    print(f"devices: {devs}", flush=True)
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devs[0].platform!r}); "
              "refusing to run on another backend", file=sys.stderr)
        return 2
    phases = Phases(jax)
    if args.four_chips:
        if len(devs) < 4:
            print(f"chip_smoke --four-chips: {len(devs)} device(s)",
                  file=sys.stderr)
            return 2
        phases.run("four_chips_sharded_window", phase_four_chips)
    else:
        phases.run("b_paper_solve", phase_paper_solve)
        phases.run("c_kernels", phase_kernels)
        phases.run("d_train_whisper_base", phase_train)
        phases.run("e_serve_whisper_base", phase_serve)
    if phases.failed:
        print(f"chip_smoke: failed phases {phases.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
