"""``SolveServer`` — request-driven damped-Fisher solves against the
resident factorization.

The request path costs two passes over S plus n-sized triangular work —
never a Gram, never a refactorization:

* microbatches whose requests all sit at the resident λ₀ reuse the
  resident factor L directly (one multi-RHS ``CholFactorization.solve``);
* mixed-λ microbatches go through ``solve_batch`` — per-column Cholesky
  of the *cached* W (O(k·n³), no S pass) with the two S passes still
  coalesced across the batch, the serving form of the ``with_damping``
  multi-λ identity.

Both paths run as one jitted function over the ``ServeState`` pytree
(bucketed RHS widths keep the compile count at O(log max_requests)).
``policy="refactorize"`` flips the same function to rebuild the Gram
every microbatch — the per-request-refactorize baseline that
``benchmarks/serve.py`` gates the cached path against.

Between microbatches (off the request path) the server hands adaptation
rows to ``OnlineAdaptation`` and lets its age/drift policy decide on a
full refresh; per-request wall-clock latencies land in ``ServerMetrics``
(p50/p99, requests/sec).

With a ``TenantManager`` attached (``tenants=``), ``submit(tenant=...)``
routes the request through that tenant's rank-r delta: the batcher
coalesces per-tenant microbatches and ``_serve`` swaps the tenant's
factor L_t in for the resident L — same S passes, same fused kernel,
same jitted function (L is just an argument). A tenant request's
``rows`` fold into the *tenant's delta*, never the shared window; a
tenant-less request behaves exactly as before, solving (and folding)
against the shared base.
"""
from __future__ import annotations

import functools
import time
from collections import deque
from typing import Any, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.solvers import CholFactorization, chol_factorize
from repro.kernels import ops as kernel_ops
from repro.obs.trace import span
from repro.serve.adapt import OnlineAdaptation
from repro.serve.batcher import Microbatch, TokenBudgetBatcher
from repro.serve.state import ServeState, as_factorization, serve_mode

__all__ = ["SolveResult", "ServerMetrics", "SolveServer"]

_HI = jax.lax.Precision.HIGHEST


class SolveResult(NamedTuple):
    uid: int
    x: Any                     # (m,) flat or tuple of per-block pieces
    damping: float
    latency_s: float


def _rows_k(rows) -> int:
    """Row count of one request's adaptation payload (digest shape tag)."""
    first = rows[0] if isinstance(rows, (tuple, list)) else rows
    return int(first.shape[0])


@functools.partial(jax.jit,
                   static_argnames=("mode", "jitter", "uniform", "monitor",
                                    "refactorize", "fused"))
def _coalesced_solve(S, W, L, lam0, V, lams, *, mode, jitter, uniform,
                     monitor, refactorize, fused=True):
    """One microbatch: x_j = (SᵀS + λ_j I)⁻¹ v_j, plus the monitored
    relative residual (−1 when off / not applicable).

    The cached uniform-λ path without drift monitoring — the serving fast
    path — dispatches to ``kernels.ops.serve_solve``: the fused resident-L
    Pallas kernel on TPU, the identical-algebra jnp reference elsewhere.
    ``fused=False`` forces the compositional ``CholFactorization.solve``
    (the benchmark baseline the fused kernel is gated against)."""
    if refactorize:
        # the baseline: a fresh O(n²·m) Gram + O(n³) Cholesky per microbatch
        fac = chol_factorize(S, lam0, mode=mode, jitter=jitter)
    else:
        if fused and uniform and not monitor and mode == "real":
            x = kernel_ops.serve_solve(S, L, V, lam0)
            return x, -jnp.ones((), jnp.float32)
        fac = CholFactorization(S=S, mode=mode, W=W, L=L, lam=lam0,
                                jitter=jitter, take_real_v=False,
                                precision=_HI)
    if uniform:
        if monitor:
            x, stats = fac.solve(V, return_stats=True)
            return x, stats.residual_norm.astype(jnp.float32)
        return fac.solve(V), -jnp.ones((), jnp.float32)
    # mixed per-request λ: drift monitoring needs a single λ — skip it
    return fac.solve_batch(V, lams, jitter=jitter), \
        -jnp.ones((), jnp.float32)


class ServerMetrics:
    """Per-request wall-clock accounting (eager, python-side).

    The per-request buffer is a fixed-size ring (``window`` most recent
    requests — a long-lived server no longer grows without bound);
    totals (``served``, token throughput, first-submit/last-done span)
    keep counting past the ring. With a ``repro.obs`` registry attached
    every record also lands in mergeable instruments —
    ``<prefix>.request_latency_s`` / ``<prefix>.queue_wait_s``
    histograms plus ``<prefix>.requests`` / ``<prefix>.tokens``
    counters — so a fleet of processes folds into one view
    (``obs.merge``) with percentiles from merged buckets. ``summary()``
    keeps its historical shape; its p50/p99 cover the ring window.
    """

    def __init__(self, *, window: int = 4096, registry=None,
                 prefix: str = "serve"):
        if window < 1:
            raise ValueError("window must be >= 1")
        self.window = int(window)
        self.registry = registry
        self.prefix = prefix
        self.reset()

    def reset(self) -> None:
        # ring of (t_submit, t_done, tokens); totals survive eviction
        self._ring: deque = deque(maxlen=self.window)
        self._count = 0
        self._tokens = 0
        self._t0: Optional[float] = None
        self._t1: Optional[float] = None

    def record(self, t_submit: float, t_done: float, tokens: int,
               queue_s: Optional[float] = None) -> None:
        self._ring.append((t_submit, t_done, tokens))
        self._count += 1
        self._tokens += tokens
        self._t0 = t_submit if self._t0 is None else min(self._t0, t_submit)
        self._t1 = t_done if self._t1 is None else max(self._t1, t_done)
        reg = self.registry
        if reg is not None:
            p = self.prefix
            reg.counter(f"{p}.requests").inc()
            reg.counter(f"{p}.tokens").inc(int(tokens))
            reg.histogram(f"{p}.request_latency_s").observe(t_done - t_submit)
            if queue_s is not None:
                reg.histogram(f"{p}.queue_wait_s").observe(max(queue_s, 0.0))

    @property
    def served(self) -> int:
        return self._count

    def latencies_s(self) -> np.ndarray:
        return np.asarray([d - s for s, d, _ in self._ring], np.float64)

    def summary(self) -> dict:
        """p50/p99 latency (over the ring window), requests/sec and
        tokens/sec over the full recorded span (first submit → last
        completion, all requests ever recorded)."""
        if not self._count:
            return {"served": 0, "p50_ms": None, "p99_ms": None,
                    "rps": None, "tokens_per_s": None}
        lat = self.latencies_s()
        span = max(self._t1 - self._t0, 1e-12)
        return {"served": self._count,
                "p50_ms": float(np.percentile(lat, 50) * 1e3),
                "p99_ms": float(np.percentile(lat, 99) * 1e3),
                "rps": self._count / span,
                "tokens_per_s": self._tokens / span}


class SolveServer:
    """The serving front end: submit → coalesce → solve → adapt.

    Args:
      state: resident ``ServeState`` (see ``init_serve_state``).
      batcher: request coalescing policy (default token-budget FIFO).
      adaptation: optional ``OnlineAdaptation`` — requests carrying score
        rows then fine-tune the window after their solve.
      policy: "cached" (resident factor, the subsystem's point) or
        "refactorize" (fresh Gram every microbatch — benchmark baseline).
      monitor_drift: compute the cheap relative residual on uniform-λ
        microbatches (feeds the drift-refresh threshold).
      jitter: extra diagonal, as elsewhere.
      fused: route cached uniform-λ microbatches (monitoring off) through
        the fused resident-L serve kernel; False forces the compositional
        solve — the baseline ``benchmarks/serve.py`` gates against.
      tenants: optional ``TenantManager`` — enables ``submit(tenant=)``.
      registry: optional ``repro.obs`` MetricsRegistry — per-request
        latency/queue-wait histograms, stage counters, and queue gauges
        land there (mergeable across processes). None: wall-clock summary
        only, zero registry overhead.
      tracer: optional ``repro.obs.Tracer`` — the spans the server
        always writes to the profiler (``serve.dispatch``, ``serve.wait``,
        the adaptation's ``adapt.*``) also become Chrome events, beside
        per-request ``request``/``queue_wait`` events; trace ids ride
        ``submit(trace=)``.
      health: optional ``repro.obs.HealthMonitor`` — propagated to the
        adaptation (margin/audit events) and re-evaluated per flush, so
        the verdict tracks the freshest numerical-health gauges.
      recorder: optional ``repro.obs.FlightRecorder`` — per-request
        digests land at the response boundary and the recorder observes
        the state (snapshot/fingerprint cadence + verdict-transition
        capture) once per flush, at the host sync the flush already
        paid for.
    """

    def __init__(self, state: ServeState, *,
                 batcher: Optional[TokenBudgetBatcher] = None,
                 adaptation: Optional[OnlineAdaptation] = None,
                 policy: str = "cached", monitor_drift: bool = True,
                 jitter: float = 0.0, fused: bool = True,
                 tenants=None, clock=time.perf_counter,
                 registry=None, tracer=None, health=None,
                 recorder=None, metrics_window: int = 4096):
        if policy not in ("cached", "refactorize"):
            raise ValueError(f"policy must be 'cached' or 'refactorize', "
                             f"got {policy!r}")
        self.state = state
        self.batcher = batcher if batcher is not None else TokenBudgetBatcher()
        self.adaptation = adaptation
        self.policy = policy
        self.monitor_drift = bool(monitor_drift)
        self.jitter = float(jitter)
        self.fused = bool(fused)
        self.tenants = tenants
        self.clock = clock
        self.registry = registry
        self.tracer = tracer
        self.health = health
        self.recorder = recorder
        self.metrics = ServerMetrics(window=metrics_window,
                                     registry=registry, prefix="serve")
        # propagate the registry to attached components that predate it
        if registry is not None and tenants is not None \
                and getattr(tenants, "registry", None) is None:
            tenants.registry = registry
        if registry is not None and adaptation is not None \
                and getattr(adaptation, "registry", None) is None:
            adaptation.registry = registry
        if health is not None and adaptation is not None \
                and getattr(adaptation, "health", None) is None:
            adaptation.health = health
        if tracer is not None and adaptation is not None \
                and getattr(adaptation, "tracer", None) is None:
            adaptation.tracer = tracer

    # -- request intake ----------------------------------------------------
    def submit(self, v, *, damping: Optional[float] = None, tokens: int = 1,
               rows=None, payload=None, tenant: Optional[str] = None,
               trace: Optional[str] = None) -> int:
        """Enqueue one request; returns its uid. ``damping=None`` means
        the resident λ₀ (the fast path). ``tenant`` solves against (and
        folds ``rows`` into) that tenant's delta — needs ``tenants=``.
        ``trace`` tags the request's spans with a caller-chosen trace id
        (the fleet dispatcher's cross-process stitching handle)."""
        if tenant is not None and self.tenants is None:
            raise RuntimeError("tenant= requires a TenantManager "
                               "(SolveServer(tenants=...))")
        lam = float(self.state.lam0) if damping is None else float(damping)
        req = self.batcher.submit(v, damping=lam, tokens=tokens, rows=rows,
                                  payload=payload, tenant=tenant, trace=trace)
        req.t_submit = self.clock()
        if self.registry is not None:
            qs = self.batcher.queue_stats(req.t_submit)
            self.registry.gauge("serve.queue_depth").set(qs["depth"])
            self.registry.gauge("serve.queue_oldest_age_s").set(
                qs["oldest_age_s"])
        return req.uid

    def solve_one(self, v, *, damping: Optional[float] = None, tokens: int = 1,
                  rows=None, tenant: Optional[str] = None):
        """Convenience: submit + flush a single request, return its x.

        Only valid on an empty queue — flushing would also solve any
        pending requests, whose results this method has no way to hand
        back; use ``submit``/``flush`` for real traffic.
        """
        if len(self.batcher):
            raise RuntimeError(
                f"solve_one with {len(self.batcher)} request(s) pending "
                "would drop their results; use submit() + flush()")
        uid = self.submit(v, damping=damping, tokens=tokens, rows=rows,
                          tenant=tenant)
        (res,) = [r for r in self.flush() if r.uid == uid]
        return res.x

    # -- the serve loop ----------------------------------------------------
    def flush(self, *, damping_state=None) -> List[SolveResult]:
        """Drain the batcher: solve every pending microbatch, fold each
        request's adaptation rows, and let the staleness policy decide on
        a refresh between microbatches. Returns results FIFO."""
        out: List[SolveResult] = []
        for mb in self.batcher.drain():
            out.extend(self._serve(mb))
            for req in mb.requests:
                if req.rows is None:
                    continue
                if mb.tenant is not None:
                    # tenant-private fine-tuning: fold into the delta,
                    # never the shared window
                    self.tenants.fold(self.state, mb.tenant, req.rows)
                elif self.adaptation is not None:
                    self.state = self.adaptation.fold(self.state, req.rows,
                                                      trace=req.trace)
            if self.adaptation is not None:
                self.state, _ = self.adaptation.maybe_refresh(
                    self.state, damping_state=damping_state)
            if self.registry is not None:
                self._health_gauges()
        if self.health is not None:
            self.health.evaluate()
        if self.recorder is not None:
            # the flush already synchronized on its solves; the recorder
            # tick (snapshot upkeep, cadenced fingerprint, verdict-
            # transition capture) rides the same boundary
            self.recorder.observe(self.state, adaptation=self.adaptation,
                                  health=self.health, registry=self.registry,
                                  tracer=self.tracer)
        return out

    def _health_gauges(self) -> None:
        """Curvature-health gauges (fold/refresh *counters* live in
        ``OnlineAdaptation``, python-side). The scalar pulls here ride a
        flush that already synchronized on the solve results."""
        reg = self.registry
        reg.gauge("curvature.factor_age").set(int(self.state.age))
        reg.gauge("curvature.last_drift_residual").set(
            float(self.state.stats.last_residual))

    def _serve_tenant(self, mb: Microbatch):
        """Solve one tenant microbatch: the same coalesced solve with the
        tenant's factor L_t swapped in for the resident L (the S passes —
        and the fused kernel — only ever see the shared window). Drift
        monitoring is skipped: the residual check is defined against the
        base system, not the tenant's reweighted one."""
        st = self.state
        lam0 = float(st.lam0)
        lams = sorted({r.damping for r in mb.requests})
        blocked = isinstance(mb.V, (tuple, list))

        def solve_at(lam: float, V, dampings):
            L_t = self.tenants.factor(
                st, mb.tenant, lam=None if lam == lam0 else lam)
            x, _ = _coalesced_solve(
                st.S, st.W, L_t, jnp.asarray(lam, st.lam0.dtype), V,
                dampings, mode=serve_mode(st), jitter=self.jitter,
                uniform=True, monitor=False, refactorize=False,
                fused=self.fused)
            return x

        if len(lams) == 1:
            return solve_at(lams[0], mb.V, mb.dampings)
        # mixed λ within one tenant: L_t must be rebuilt per λ anyway, so
        # solve per-unique-λ column groups (eager slow path) and reassemble
        cols: dict = {}
        for lam in lams:
            idx = [j for j, r in enumerate(mb.requests) if r.damping == lam]
            Vg = tuple(vb[:, idx] for vb in mb.V) if blocked \
                else mb.V[:, idx]
            lg = jnp.full((len(idx),), lam, jnp.float32)
            xg = solve_at(lam, Vg, lg)
            for a, j in enumerate(idx):
                cols[j] = tuple(xb[:, a] for xb in xg) if blocked \
                    else xg[:, a]
        if blocked:
            return tuple(
                jnp.stack([cols[j][b] for j in range(mb.k)], axis=1)
                for b in range(len(mb.V)))
        return jnp.stack([cols[j] for j in range(mb.k)], axis=1)

    def _serve(self, mb: Microbatch) -> List[SolveResult]:
        st = self.state
        lam0 = float(st.lam0)
        t_start = self.clock()
        with span("serve.dispatch", tracer=self.tracer, k=mb.k,
                  uids=[r.uid for r in mb.requests]):
            if mb.tenant is not None:
                x = self._serve_tenant(mb)
                resid = -jnp.ones((), jnp.float32)
            else:
                uniform = all(r.damping == lam0 for r in mb.requests)
                x, resid = _coalesced_solve(
                    st.S, st.W, st.L, st.lam0, mb.V, mb.dampings,
                    mode=serve_mode(st), jitter=self.jitter, uniform=uniform,
                    monitor=self.monitor_drift and self.policy == "cached",
                    refactorize=self.policy == "refactorize", fused=self.fused)
        with span("serve.wait", tracer=self.tracer):
            jax.block_until_ready(x)
        t_done = self.clock()

        k = mb.k
        stats = st.stats._replace(
            served=st.stats.served + jnp.asarray(k, jnp.int32),
            microbatches=st.stats.microbatches + 1,
            last_residual=jnp.where(resid >= 0, resid,
                                    st.stats.last_residual))
        self.state = st._replace(age=st.age + 1, stats=stats)

        if self.registry is not None:
            self.registry.counter("serve.microbatches").inc()
            self.registry.histogram("serve.solve_latency_s").observe(
                t_done - t_start)

        results = []
        mb_resid = float(resid) if self.recorder is not None else None
        for j, req in enumerate(mb.requests):
            xj = tuple(xb[:, j] for xb in x) if isinstance(x, (tuple, list)) \
                else x[:, j]
            queue_s = max(t_start - req.t_submit, 0.0) \
                if req.t_submit > 0.0 else None
            self.metrics.record(req.t_submit, t_done, req.tokens,
                                queue_s=queue_s)
            if self.recorder is not None:
                self.recorder.record_request(
                    req.uid, tenant=mb.tenant, damping=req.damping,
                    tokens=req.tokens,
                    k_rows=0 if req.rows is None else _rows_k(req.rows),
                    latency_s=t_done - req.t_submit,
                    residual=mb_resid if mb_resid >= 0 else None)
            if self.tracer is not None and queue_s is not None:
                self.tracer.record("queue_wait", req.t_submit,
                                   req.t_submit + queue_s, cat="queue",
                                   trace=req.trace, args={"uid": req.uid})
                self.tracer.record("request", req.t_submit, t_done,
                                   cat="serve", trace=req.trace,
                                   args={"uid": req.uid})
            results.append(SolveResult(uid=req.uid, x=xj,
                                       damping=req.damping,
                                       latency_s=t_done - req.t_submit))
        return results

    # -- maintenance -------------------------------------------------------
    def apply_fold(self, rows, *, slots=None, record: bool = True) -> None:
        """Apply one fold event to the resident window outside the request
        path — the gossip-replay entry point (``repro.fleet``): a remote
        replica's fold columns enter this window through the same
        ``replace_factors`` algebra as local ones. ``slots`` (from the
        event) are verified against the local FIFO cursor."""
        if self.adaptation is None:
            raise RuntimeError("apply_fold needs an OnlineAdaptation")
        self.state = self.adaptation.fold(self.state, rows, slots=slots,
                                          record=record)

    def refresh(self) -> None:
        """Force a full refactorization now (ops hook; not request-path)."""
        if self.adaptation is not None:
            self.state, _ = self.adaptation.maybe_refresh(self.state,
                                                          force=True)
        else:
            fac = chol_factorize(self.state.S, self.state.lam0,
                                 mode=serve_mode(self.state),
                                 jitter=self.jitter)
            self.state = self.state._replace(
                W=fac.W, L=fac.L, age=jnp.zeros((), jnp.int32),
                stats=self.state.stats._replace(
                    refreshes=self.state.stats.refreshes + 1))

    @property
    def factorization(self) -> CholFactorization:
        """The resident factorization, as a first-class solver object."""
        return as_factorization(self.state, jitter=self.jitter)

    @property
    def stats(self):
        return self.state.stats
