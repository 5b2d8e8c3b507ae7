"""Driver: one jitted Algorithm-1 solve per call, closed loop.

The system under test is ``repro.core.chol_solve(S, v, λ)`` under
``jax.jit``: the Gram of the window, its Cholesky factor and the two
passes over S, every call (the refactor path a trainer at large n pays
each step, and the paper's Table-1 measurement). S is drawn once on the
device from the seed; each call gets a new right-hand side, also made on
the device from the seed, and its answer is waited for before the next
call. The answers of every ``keep_every``-th call are kept; after the
window a sample of them drawn from the seed is compared with the plain
reference.
"""
from __future__ import annotations

import time

import numpy as np


class SolveCell:
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp

        from repro.core import chol_solve

        self.ctx = ctx
        c, t = ctx.config, ctx.traffic
        self.n, self.m = int(c["n"]), int(c["m"])
        self.lam = float(c["damping"])
        dtype = jnp.dtype(c["dtype"])
        lam, n, m = self.lam, self.n, self.m

        def chol_solve_call(S, v):
            return chol_solve(S, v, lam)

        self.solve = jax.jit(chol_solve_call)
        self.make_window = jax.jit(
            lambda k: ctx.ref.make_window(k, n, m, dtype))
        self.make_rhs = jax.jit(lambda k, i: jax.random.normal(
            jax.random.fold_in(k, i), (m,), jnp.float32))
        self.rhs_base = ctx.key(2)
        self.keep_every = int(t["keep_every"])
        self.S = self.make_window(ctx.key(0))
        self.kept = {}
        self.calls = 0
        for _ in range(2):                       # compile and warm
            self.solve(self.S, self.rhs(-1)).block_until_ready()

    def rhs(self, i):
        return self.make_rhs(self.rhs_base, np.int32(i))

    def window(self, seconds):
        import jax
        t0 = time.perf_counter()
        i, bad = 0, 0
        v = self.rhs(0)
        while True:
            with jax.profiler.TraceAnnotation("bench.solve"):
                x = self.solve(self.S, v)
            # the next call's v is made while this one runs
            with jax.profiler.TraceAnnotation("bench.make_rhs"):
                v = self.rhs(i + 1)
            with jax.profiler.TraceAnnotation("bench.wait"):
                x.block_until_ready()
            if i % self.keep_every == 0:
                self.kept[i] = x
            i += 1
            el = time.perf_counter() - t0
            if el >= seconds:
                break
        self.calls = i
        self.window_s = el
        bad = sum(int(not np.isfinite(np.asarray(x)).all())
                  for x in self.kept.values())
        return {"attempted": i, "failed": bad,
                "metrics": {"solve_ms": el / i * 1e3},
                "notes": [f"solve: {i} calls in {el:.3f} s"]}

    def release(self):
        self.kept = {i: np.asarray(x) for i, x in self.kept.items()}
        self.S = None

    def sample(self):
        """Kept calls compared: a seeded sample, and the last kept one."""
        idx = sorted(self.kept)
        rng = np.random.default_rng(self.ctx.seed)
        k = min(int(self.ctx.traffic["check_sample"]), len(idx))
        pick = set(rng.choice(idx, size=k, replace=False).tolist())
        pick.add(idx[-1])
        return sorted(pick)

    def answers(self, idx, precision="highest"):
        """The reference's answers to the calls ``idx``."""
        import jax
        import jax.numpy as jnp
        ref, ctx = self.ctx.solve_ref, self.ctx
        S = self.make_window(ctx.key(0))
        V = jnp.stack([self.rhs(i) for i in idx], axis=1)
        L = jax.jit(ref.factor, static_argnums=2)(S, self.lam, precision)
        X = jax.jit(ref.solve, static_argnums=4)(S, L, V, self.lam, precision)
        return np.asarray(X, np.float64)

    def readings(self, X, Xr):
        err = np.linalg.norm(X - Xr, axis=0) / np.linalg.norm(Xr, axis=0)
        return {"solve_rel_err": float(np.max(err))}

    def check(self):
        idx = self.sample()
        X = np.stack([self.kept[i] for i in idx], axis=1).astype(np.float64)
        Xr = self.answers(idx)
        lim = self.ctx.limits
        return {k: (v, float(lim.get(k, float("nan"))))
                for k, v in self.readings(X, Xr).items()}


def setup(ctx):
    return SolveCell(ctx)


def calibrate(make_ctx, seeds, seconds, log):
    """Readings behind the limit, on each seed, after a short window at the
    cell's own load: the program against the reference (lower) and the
    reference at the precisions below (the control, upper)."""
    cell = None
    for seed in seeds:
        ctx = make_ctx(seed)
        if cell is None:
            cell = SolveCell(ctx)
        else:
            cell.ctx, cell.kept = ctx, {}
            cell.rhs_base = ctx.key(2)
            cell.S = cell.make_window(ctx.key(0))
        cell.window(seconds)
        cell.release()
        idx = cell.sample()
        X = np.stack([cell.kept[i] for i in idx], axis=1).astype(np.float64)
        Xr = cell.answers(idx)
        row = {"seed": seed, "calls": cell.calls, "compared": len(idx),
               "program": cell.readings(X, Xr)}
        for p in ("high", "default"):
            row["control_" + p] = cell.readings(cell.answers(idx, p), Xr)
        yield row
