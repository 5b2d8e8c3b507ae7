"""Driver: the async solve server under open-loop arrivals.

The system under test is ``repro.dist.AsyncSolveServer`` over a resident
score window S (n, m) drawn on the device from the seed (N(0, 1/m)),
replicated on one chip or laid out 1d over the cell's chips, with the
serving defaults of ``build_server``/``serve_main``: token-budget
batching, online adaptation (folds, age and drift refreshes, audits) and
the metrics registry with the health monitor.

Traffic (``bench/traffic/<mix>.json``): a fixed number of requests,
``rate_per_s`` × seconds, due at sorted uniform times over the window
(a Poisson process given its count, so every seed offers the same work in
another order); a fixed share at each λ of ``lambda_mix``; a fixed share
carrying ``fold_rows`` score rows to fold; right-hand sides made on the
device from the seed per request, or drawn from a pool of ``rhs_pool``
vectors held on the device. The generator submits each request at its
due time from the main thread, a collector thread takes the answers in
order, and a request's latency runs from its due time to its answer on
the host.

After the window the plain reference replays the window's history in the
order the server formed its microbatches (which the recording batcher
logs), folds included, and answers a seeded sample of the served
requests.
"""
from __future__ import annotations

import threading
import time

import numpy as np


def _make_recording_batcher(base):
    class RecordingBatcher(base):
        """The program's batcher; logs each microbatch's request uids."""

        def __init__(self, **kw):
            super().__init__(**kw)
            self.log = []

        def next_microbatch(self):
            mb = super().next_microbatch()
            if mb is not None:
                self.log.append(tuple(r.uid for r in mb.requests))
            return mb

    return RecordingBatcher


class Schedule:
    """The window's requests: due offsets, λ multiples, folds, rhs ids."""

    def __init__(self, t, seed, seconds):
        rng = np.random.default_rng([seed & (2 ** 64 - 1), 7])
        self.n = int(round(float(t["rate_per_s"]) * seconds))
        self.due = np.sort(rng.uniform(0.0, seconds, self.n))
        mults, weights = zip(*t["lambda_mix"])
        counts = np.floor(np.asarray(weights) / sum(weights) * self.n)
        counts[0] += self.n - counts.sum()
        lam = np.repeat(np.asarray(mults, np.float64), counts.astype(int))
        self.lam_mult = rng.permutation(lam)
        n_fold = int(self.n * float(t.get("fold_share", 0.0)))
        self.fold = np.zeros(self.n, bool)
        self.fold[rng.permutation(self.n)[:n_fold]] = True
        pool = t.get("rhs_pool")
        self.rhs = rng.integers(0, pool, self.n) if pool else None


class ServeCell:
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.obs import HealthMonitor, MetricsRegistry
        from repro.serve import OnlineAdaptation, TokenBudgetBatcher

        self.ctx = ctx
        c, t = ctx.config, ctx.traffic
        self.n = int(t.get("window_n") or c["n"])
        self.m = int(c.get("m") or c["num_parameters"])
        self.lam0 = float(t.get("damping", c.get("damping")))
        self.k_rows = int(t.get("fold_rows", 0))
        self.layout = t["layout"]
        n, m = self.n, self.m
        if self.layout == "replicated":
            dev = ctx.devices[0]
            s_sh = v_sh = jax.sharding.SingleDeviceSharding(dev)
            self.mesh = None
        else:
            from repro.launch.mesh import make_mesh
            self.mesh = make_mesh((ctx.chips,), ("model",))
            s_sh = NamedSharding(self.mesh, P(None, "model"))
            v_sh = NamedSharding(self.mesh, P("model"))
        self.make_window = jax.jit(
            lambda k: jax.random.normal(k, (n, m), jnp.float32) / np.sqrt(m),
            out_shardings=s_sh)
        self.make_rhs = jax.jit(
            lambda k, i: jax.random.normal(jax.random.fold_in(k, i), (m,),
                                           jnp.float32),
            out_shardings=v_sh)
        self.make_rows = jax.jit(
            lambda k, i: jax.random.normal(
                jax.random.fold_in(k, i), (max(self.k_rows, 1), m),
                jnp.float32) / np.sqrt(m),
            out_shardings=s_sh)
        self.rhs_base, self.rows_base = ctx.key(3), ctx.key(4)
        pool = t.get("rhs_pool")
        self.pool = [self.make_rhs(ctx.key(5), np.int32(i))
                     for i in range(pool)] \
            if pool else None

        self.registry = MetricsRegistry()
        self.health = HealthMonitor(self.registry)
        Batcher = _make_recording_batcher(TokenBudgetBatcher)
        self.batcher = Batcher(max_tokens=int(t["max_tokens"]),
                               max_requests=int(t["max_requests"]))
        self.adaptation = OnlineAdaptation(
            refresh_every=int(t["refresh_every"]),
            drift_frac=float(t["drift_frac"]),
            audit_every=int(t["audit_every"]))
        self.reqs = {}          # uid -> (request id g, λ, folds?)
        self.g = 0              # global request counter (warm-up included)
        self.server = self._build_server(self.make_window(ctx.key(0)))
        self.warm()

    def _build_server(self, S):
        from repro.dist import (AsyncSolveServer, DistSpec,
                                init_sharded_serve_state)
        from repro.serve import init_serve_state
        if self.mesh is None:
            state = init_serve_state(S, self.lam0)
        else:
            state = init_sharded_serve_state(
                S, self.lam0, spec=DistSpec(self.mesh, "1d"))
        return AsyncSolveServer(state, batcher=self.batcher,
                                adaptation=self.adaptation,
                                registry=self.registry, health=self.health)

    # -- requests -----------------------------------------------------------
    def rhs(self, g, pool_id=None):
        if self.pool is not None:
            return self.pool[pool_id]
        return self.make_rhs(self.rhs_base, np.int32(g))

    def payload(self, g, fold, pool_id=None):
        """Request g's right-hand side and fold rows, made on the device."""
        v = self.rhs(g, pool_id)
        rows = self.make_rows(self.rows_base, np.int32(g)) if fold else None
        return v, rows

    def submit(self, lam_mult, fold, pool_id=None, payload=None):
        g = self.g
        self.g += 1
        v, rows = payload if payload is not None \
            else self.payload(g, fold, pool_id)
        lam = self.lam0 * lam_mult
        uid = self.server.submit(v, damping=None if lam_mult == 1 else lam,
                                 rows=rows)
        self.reqs[uid] = (g, lam, bool(fold), pool_id)
        return uid

    def warm(self):
        """Compile every program the window runs: each microbatch width
        from 1 to max_requests, uniform and mixed λ, with a fold, then
        single requests until an age refresh and an audit have run."""
        t = self.ctx.traffic
        k_max = int(t["max_requests"])
        mults = [m for m, _ in t["lambda_mix"]]
        other = next((m for m in mults if m != 1), None)
        folds = self.k_rows > 0 and float(t.get("fold_share", 0)) > 0
        pool = self.pool is not None
        rounds = []
        for k in range(1, k_max + 1):
            rounds.append([1] * k)
            if other is not None:
                rounds.append([other] + [1] * (k - 1))
        for i, r in enumerate(rounds):
            # hold the server's lock while the round is queued, so the
            # worker takes all of it as one microbatch of width len(r)
            with self.server._cv:
                uids = [self.submit(mult, folds and j == 0,
                                    (i + j) % len(self.pool) if pool else None)
                        for j, mult in enumerate(r)]
            for u in uids:
                self.server.result(u, timeout=600)
        for i in range(int(t["refresh_every"]) + 2 * int(t["audit_every"])):
            u = self.submit(1, False, i % len(self.pool) if pool else None)
            self.server.result(u, timeout=600)
        snap = self.registry.snapshot()
        if self.adaptation.refresh_every and \
                snap["counters"].get("curvature.refreshes", 0) < 1:
            raise RuntimeError("warm-up ran no refresh")

    def prepare(self, seconds):
        """The window's schedule and every request's payload, made before
        the window so that the generator does nothing at a due time but
        submit; and the requests whose answers are compared, drawn from
        the seed (only they are held: each holds its microbatch's X)."""
        import jax
        ctx = self.ctx
        sch = self.schedule = Schedule(ctx.traffic, ctx.seed, seconds)
        rng = np.random.default_rng([ctx.seed & (2 ** 64 - 1), 11])
        k = min(int(ctx.traffic["check_sample"]), sch.n)
        self.keep = set(rng.choice(sch.n, size=k, replace=False).tolist())
        self.keep.add(sch.n - 1)
        self.pids = [None if sch.rhs is None else int(sch.rhs[i])
                     for i in range(sch.n)]
        self.payloads = [self.payload(self.g + i, bool(sch.fold[i]),
                                      self.pids[i]) for i in range(sch.n)]
        jax.block_until_ready(self.payloads)
        self.prepared = (seconds, ctx.traffic["rate_per_s"])

    # -- the window -----------------------------------------------------------
    def window(self, seconds):
        import jax
        ctx = self.ctx
        if getattr(self, "prepared", None) != \
                (seconds, ctx.traffic["rate_per_s"]):
            self.prepare(seconds)
        sch, keep, pids, payloads = self.schedule, self.keep, self.pids, \
            self.payloads
        self.payloads = self.prepared = None
        uids = [None] * sch.n
        t_ans = np.full(sch.n, np.nan)
        results = {}
        errors = []
        cv = threading.Condition()
        n_sub = [0]
        snap0 = self.registry.snapshot()
        t0 = self.t0 = time.perf_counter() + 0.05
        deadline = t0 + seconds + 60.0

        def collect():
            for i in range(sch.n):
                with cv:
                    cv.wait_for(lambda: n_sub[0] > i)
                try:
                    r = self.server.result(
                        uids[i], timeout=max(deadline - time.perf_counter(),
                                             0.001))
                    t_ans[i] = time.perf_counter()
                    if i in keep:
                        results[i] = r.x
                except Exception as e:       # never answered, or failed
                    errors.append((i, repr(e)))
                    if time.perf_counter() > deadline:
                        break

        th = threading.Thread(target=collect, name="bench-collector",
                              daemon=True)
        th.start()
        late = np.zeros(sch.n)
        for i in range(sch.n):
            due = t0 + sch.due[i]
            now = time.perf_counter()
            if due > now:
                time.sleep(due - now)
            late[i] = time.perf_counter() - due
            with jax.profiler.TraceAnnotation("bench.submit"):
                uids[i] = self.submit(float(sch.lam_mult[i]),
                                      bool(sch.fold[i]), pids[i],
                                      payloads[i])
                payloads[i] = None
            with cv:
                n_sub[0] = i + 1
                cv.notify()
        th.join(timeout=max(deadline - time.perf_counter(), 0) + 5)
        snap1 = self.registry.snapshot()
        t_end = t0 + seconds
        lat = t_ans - (t0 + sch.due)
        ok = np.isfinite(lat)
        self.uids, self.results = uids, results
        self.last_latencies = lat
        self.win_stats = _delta(snap0, snap1)
        answered_in = int(np.sum(t_ans <= t_end))
        p95 = float(np.percentile(lat[ok], 95)) * 1e3 if ok.any() \
            else float("nan")
        c = self.win_stats["counters"]
        notes = [
            f"serve: {sch.n} requests due, {int(ok.sum())} answered, "
            f"{answered_in} inside the window; p50 "
            f"{np.percentile(lat[ok], 50) * 1e3:.3f} ms, p95 {p95:.3f} ms, "
            f"max {np.max(lat[ok]) * 1e3:.3f} ms",
            f"generator lateness: p50 {np.percentile(late, 50) * 1e6:.1f} us,"
            f" p99 {np.percentile(late, 99) * 1e6:.1f} us, "
            f"max {np.max(late) * 1e6:.1f} us",
            f"server: {c.get('serve.microbatches', 0)} microbatches, "
            f"{c.get('curvature.folds', 0)} folds, "
            f"{c.get('curvature.refreshes', 0)} refreshes in the window "
            f"({c.get('curvature.refresh_age', 0)} for age, "
            f"{c.get('curvature.refresh_drift', 0)} for drift)"]
        if errors:
            notes.append(f"errors: {errors[:3]}")
        return {"attempted": sch.n, "failed": sch.n - int(ok.sum()),
                "metrics": {"request_p95_ms": p95,
                            "solves_per_s": answered_in / seconds},
                "notes": notes}

    def release(self):
        if self.server is not None:
            self.server.shutdown(drain=True)
        self.server = None
        # compared: the sampled requests that were answered (one that was
        # not is counted in ``failed``)
        self.sample_idx = sorted(self.results)
        self.kept = {i: np.asarray(x) for i, x in self.results.items()}
        self.results = None

    # -- the check ------------------------------------------------------------
    def answers(self, idx, precision="highest"):
        """The reference's answers to window requests ``idx``: the window
        replayed through every fold the server applied before each one's
        microbatch, in the order the server formed them."""
        import jax
        import jax.numpy as jnp
        ref = self.ctx.solve_ref
        mb_of = {}
        for j, mb in enumerate(self.batcher.log):
            for u in mb:
                mb_of[u] = j
        want = {}
        for i in idx:
            want.setdefault(mb_of[self.uids[i]], []).append(i)
        S = self.make_window(self.ctx.key(0))
        slot = 0
        n, k = self.n, self.k_rows
        put = jax.jit(lambda S, R, s: jax.lax.dynamic_update_slice(
            S, R, (s, 0)), donate_argnums=0)
        factor = jax.jit(ref.factor, static_argnums=2)
        solve = jax.jit(ref.solve, static_argnums=4)
        out = {}
        for j, mb in enumerate(self.batcher.log):
            if j in want:
                by_lam = {}
                for i in want[j]:
                    g, lam, _, pid = self.reqs[self.uids[i]]
                    by_lam.setdefault(lam, []).append((i, g, pid))
                for lam, items in by_lam.items():
                    L = factor(S, lam, precision)
                    V = jnp.stack([self.rhs(g, pid) for _, g, pid in items],
                                  axis=1)
                    X = np.asarray(solve(S, L, V, lam, precision), np.float64)
                    for a, (i, _, _) in enumerate(items):
                        out[i] = X[:, a]
            for u in mb:                         # the folds after the solve
                g, _, fold, _ = self.reqs[u]
                if fold:
                    R = self.make_rows(self.rows_base, np.int32(g))
                    if slot + k <= n:
                        S = put(S, R, slot)
                    else:
                        rows = np.asarray(R)
                        for r in range(k):
                            S = put(S, jnp.asarray(rows[r:r + 1]),
                                    (slot + r) % n)
                    slot = (slot + k) % n
            if j >= max(want):
                break
        return out

    def readings(self, X, Xr):
        err = max(np.linalg.norm(X[i] - Xr[i]) / np.linalg.norm(Xr[i])
                  for i in X)
        return {"answer_rel_err": float(err)}

    def check(self):
        Xr = self.answers(self.sample_idx)
        X = {i: self.kept[i].astype(np.float64) for i in self.sample_idx}
        lim = self.ctx.limits
        return {k: (v, float(lim.get(k, float("nan"))))
                for k, v in self.readings(X, Xr).items()}


def _delta(a, b):
    out = {"counters": {}, "histograms": {}}
    for k, v in b["counters"].items():
        out["counters"][k] = v - a["counters"].get(k, 0)
    for k, h in b["histograms"].items():
        h0 = a["histograms"].get(k)
        counts = list(h["counts"]) if h0 is None else \
            [x - y for x, y in zip(h["counts"], h0["counts"])]
        out["histograms"][k] = {"bounds": h["bounds"], "counts": counts,
                                "count": sum(counts)}
    return out


def setup(ctx):
    cell = ServeCell(ctx)
    cell.prepare(ctx.seconds)
    return cell


def calibrate(make_ctx, seeds, seconds, log):
    """Readings behind the limit, on each seed, after a short window at the
    cell's own load: the program against the reference (lower) and the
    reference at the precisions below (the control, upper)."""
    for seed in seeds:
        cell = ServeCell(make_ctx(seed))
        win = cell.window(seconds)
        cell.release()
        idx = cell.sample_idx
        Xr = cell.answers(idx)
        X = {i: cell.kept[i].astype(np.float64) for i in idx}
        row = {"seed": seed, "requests": win["attempted"],
               "failed": win["failed"], "compared": len(idx),
               "program": cell.readings(X, Xr),
               "metrics": win["metrics"], "notes": win["notes"]}
        for p in ("high", "default"):
            row["control_" + p] = cell.readings(cell.answers(idx, p), Xr)
        yield row


def sweep(make_ctx, seed, rates, seconds, log):
    """Latency at each offered rate, on one server built once."""
    cell = ServeCell(make_ctx(seed))
    for rate in rates:
        cell.ctx.traffic = dict(cell.ctx.traffic, rate_per_s=rate)
        win = cell.window(seconds)
        lat = cell.last_latencies
        q = max(len(lat) // 4, 1)
        yield {"rate_per_s": rate, "requests": win["attempted"],
               "failed": win["failed"], "metrics": win["metrics"],
               "p50_ms": float(np.nanpercentile(lat, 50) * 1e3),
               "p99_ms": float(np.nanpercentile(lat, 99) * 1e3),
               "p95_first_quarter_ms": float(np.nanpercentile(lat[:q], 95) * 1e3),
               "p95_last_quarter_ms": float(np.nanpercentile(lat[-q:], 95) * 1e3),
               "notes": win["notes"]}
    cell.release()
