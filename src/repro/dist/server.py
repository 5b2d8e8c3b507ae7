"""``AsyncSolveServer`` — concurrent serving against the sharded window.

Two things change relative to the eager ``repro.serve.SolveServer``; the
math does not:

* **Concurrency** — requests are submitted from any number of producer
  threads; a single worker thread drains the ``TokenBudgetBatcher`` and
  owns all device dispatch. Solves are dispatched asynchronously and
  ``jax.block_until_ready`` runs only at the response boundary, so the
  host coalesces/stacks the next microbatch (and producers keep
  enqueuing) while the device executes the previous one. With no
  adaptation configured the worker additionally keeps one microbatch in
  flight (dispatch i+1 before materializing i); with adaptation the
  eager fold → refresh ordering is pinned so responses stay equivalent
  to ``SolveServer.flush`` on the same trace.

* **Sharding** — with a ``ShardedServeState`` the per-microbatch
  dispatcher routes uniform-λ batches to a shard_map resident-L path and
  mixed-λ batches to a shard_map ``solve_batch`` twin: the two O(n·m·k)
  window passes run per slab with one psum each, the n-sized triangular
  work replicated — the serving analogue of
  ``core.distributed.sharded_chol_solve`` (1d, 2d, and blocked layouts).
  With a plain ``ServeState`` the worker calls the *same* jitted
  ``_coalesced_solve`` as the eager server, so replicated async responses
  are bit-identical to eager ones on identical traces.

``flush()`` keeps the eager server's API: it blocks until every request
submitted so far has been served and returns their results FIFO — so
``serve_main`` and the benchmarks drive both servers with one code path.

Multi-tenant serving composes with both layouts for free: a tenant
microbatch swaps the tenant's factor L_t (``TenantManager.factor``) in
for the resident L, and L was already a replicated ``P()`` argument of
the shard_map solve — the per-slab S passes and psums don't know or
care whose factor the triangular solves use.
"""
from __future__ import annotations

import functools
import threading
import time
from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular
from jax.sharding import PartitionSpec as P

from repro.core.operator import BlockedScores
from repro.core.shard_compat import shard_map_compat
from repro.dist.state import DistSpec, ShardedServeState
from repro.kernels import ops as kernel_ops
from repro.obs.trace import host_sync, span
from repro.serve.batcher import Microbatch, TokenBudgetBatcher
from repro.serve.server import ServerMetrics, SolveResult, \
    _coalesced_solve, _rows_k
from repro.serve.state import ServeState, as_factorization, serve_mode

__all__ = ["AsyncSolveServer", "make_sharded_coalesced_solve"]

_HI = jax.lax.Precision.HIGHEST


def _ct(A: jax.Array, mode: str) -> jax.Array:
    return A.conj().T if mode == "complex" else A.T


# ---------------------------------------------------------------------------
# the sharded coalesced solve (shard_map twin of server._coalesced_solve)
# ---------------------------------------------------------------------------

def _serve_local(S_in, W, L, lam0, V_in, lams, *, model_axis: str,
                 mode: str, jitter: float, uniform: bool, monitor: bool,
                 refactorize: bool):
    """One microbatch on the local slab. Collectives: one psum of (n, k)
    for u = S·V (plus one n² psum under policy="refactorize" and two
    scalar psums for the monitored residual); everything n-sized runs
    replicated."""
    blocked = isinstance(S_in, BlockedScores)
    S_blocks = S_in.blocks if blocked else (S_in,)
    V_blocks = tuple(V_in) if isinstance(V_in, (tuple, list)) else (V_in,)
    acc = jnp.promote_types(S_blocks[0].dtype, jnp.float32)
    S32 = tuple(b.astype(acc) for b in S_blocks)
    V32 = tuple(v.astype(jnp.promote_types(v.dtype, acc)) for v in V_blocks)
    n = W.shape[0]
    lam0 = jnp.real(jnp.asarray(lam0, acc))

    if refactorize:       # the baseline: fresh per-slab Gram psum + chol
        W = jax.lax.psum(
            sum(jnp.matmul(b, _ct(b, mode), precision=_HI) for b in S32),
            model_axis)
        L = jnp.linalg.cholesky(
            W + (lam0 + jitter) * jnp.eye(n, dtype=W.dtype))

    # the two m-sized S passes run per slab through the serve kernels
    # (fused Pallas on TPU, identical-algebra jnp reference elsewhere);
    # the psum between them is why the sharded path composes the split
    # kernels instead of the single fused invocation
    u = jax.lax.psum(
        sum(kernel_ops.sv_cross(b, v) for b, v in zip(S_blocks, V_blocks)),
        model_axis)                                           # (n, k)

    if uniform:
        w = solve_triangular(L, u, lower=True)
        w = solve_triangular(_ct(L, mode), w, lower=False)
        xs = tuple(kernel_ops.serve_apply(b, w, v, lam0)
                   for b, v in zip(S_blocks, V_blocks))
        resid = -jnp.ones((), jnp.float32)
        if monitor:
            Sx = jax.lax.psum(
                sum(jnp.matmul(b, x, precision=_HI)
                    for b, x in zip(S32, xs)), model_axis)
            r2 = sum(jnp.sum(jnp.abs(
                jnp.matmul(_ct(b, mode), Sx, precision=_HI)
                + lam0 * x - v) ** 2)
                for b, x, v in zip(S32, xs, V32))
            v2 = sum(jnp.sum(jnp.abs(v) ** 2) for v in V32)
            r2 = jax.lax.psum(r2, model_axis)
            v2 = jax.lax.psum(v2, model_axis)
            resid = jnp.sqrt(r2 / v2).astype(jnp.float32)
    else:
        # mixed per-request λ: batched chols of the cached W, one S pass
        # each way for the whole batch (solve_batch, sharded)
        lams = jnp.real(jnp.asarray(lams, acc))
        eye = jnp.eye(n, dtype=W.dtype)
        Wd = W[None] + (lams + jitter)[:, None, None] * eye   # (k, n, n)
        Ls = jnp.linalg.cholesky(Wd)
        ut = u.T[..., None]                                   # (k, n, 1)
        w = jax.vmap(lambda Lj, b: solve_triangular(Lj, b, lower=True))(
            Ls, ut)
        w = jax.vmap(lambda Lj, b: solve_triangular(
            _ct(Lj, mode), b, lower=False))(Ls, w)
        w = w[..., 0].T                                       # (n, k)
        ys = tuple(jnp.matmul(_ct(b, mode), w, precision=_HI) for b in S32)
        xs = tuple((v - y) / lams[None, :] for v, y in zip(V32, ys))
        resid = -jnp.ones((), jnp.float32)

    x = xs if blocked else xs[0]
    return x, resid


def _serve_local_2d(S_loc, W, L, lam0, V_loc, lams, *, data_axis: str,
                    **kw):
    """2d layout: all-gather the sample axis (cheap: n·m_loc words), then
    the 1d path; V/x are replicated over data, sharded over model."""
    S_cols = jax.lax.all_gather(S_loc, data_axis, axis=0, tiled=True)
    return _serve_local(S_cols, W, L, lam0, V_loc, lams, **kw)


def make_sharded_coalesced_solve(spec: DistSpec, *, mode: str,
                                 jitter: float, uniform: bool,
                                 monitor: bool, refactorize: bool):
    """Build the jitted shard_map request-path solve
    ``(S, W, L, lam0, V, lams) -> (x, resid)`` for ``spec``'s layout."""
    if spec.layout == "2d":
        body = functools.partial(
            _serve_local_2d, data_axis=spec.data_axis,
            model_axis=spec.model_axis, mode=mode, jitter=jitter,
            uniform=uniform, monitor=monitor, refactorize=refactorize)
    else:
        body = functools.partial(
            _serve_local, model_axis=spec.model_axis, mode=mode,
            jitter=jitter, uniform=uniform, monitor=monitor,
            refactorize=refactorize)
    fn = shard_map_compat(
        body, mesh=spec.mesh,
        in_specs=(spec.s_spec(), P(), P(), P(), spec.v_spec(), P()),
        out_specs=(spec.v_spec(), P()))
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# the async front end
# ---------------------------------------------------------------------------

class AsyncSolveServer:
    """Thread-safe request front end over the (optionally sharded) window.

    Args:
      state: a ``ServeState`` (replicated; responses bit-identical to the
        eager ``SolveServer``) or a ``ShardedServeState`` (requests served
        through the shard_map paths of its ``DistSpec``).
      batcher / adaptation / policy / monitor_drift / jitter: as on
        ``SolveServer``. When the state is sharded and the adaptation has
        no ``dist`` bound yet, the state's spec is bound automatically so
        folds and refreshes run through the sharded cholupdate.
      clock: latency timestamps (injectable for tests).
      registry / tracer / health: as on ``SolveServer`` — the
        async server additionally splits queue wait at the *dispatch* boundary
        (submit → dispatch vs dispatch → materialized), which is where
        the pipelining happens.

    Spans (``repro.obs.span``, always on, on the profiler's clock):
    ``serve.submit`` with its ``serve.submit.lock`` on the caller's
    thread; on the worker ``serve.idle`` while nothing is queued, and per
    microbatch one ``serve.microbatch`` step span holding ``serve.form``,
    ``serve.dispatch``, the adaptation's ``adapt.*``, ``serve.wait``,
    ``serve.results`` and ``serve.release``. Each blocking device-to-host
    read on the worker is a ``host.sync`` span and one more on the
    registry's ``serve.host_syncs``.

    The worker thread starts immediately; use as a context manager or
    call ``shutdown()`` when done.
    """

    def __init__(self, state, *,
                 batcher: Optional[TokenBudgetBatcher] = None,
                 adaptation=None, policy: str = "cached",
                 monitor_drift: bool = True, jitter: float = 0.0,
                 tenants=None, clock=time.perf_counter,
                 registry=None, tracer=None, health=None,
                 recorder=None, metrics_window: int = 4096):
        if policy not in ("cached", "refactorize"):
            raise ValueError(f"policy must be 'cached' or 'refactorize', "
                             f"got {policy!r}")
        if isinstance(state, ShardedServeState):
            self.state: ServeState = state.state
            self.spec: Optional[DistSpec] = state.spec
            # logical column widths of an uneven (zero-padded) window:
            # RHS pads up / solutions slice back at the request boundary
            self.widths: Optional[tuple] = state.widths if state.padded \
                else None
            # logical sample count: the FIFO modulus of a 2d-padded
            # window (pad rows must never be folded over)
            self.fifo_n: Optional[int] = state.n_logical
        else:
            self.state = state
            self.spec = None
            self.widths = None
            self.fifo_n = None
        self.batcher = batcher if batcher is not None else TokenBudgetBatcher()
        if adaptation is not None and self.spec is not None \
                and getattr(adaptation, "dist", None) is None:
            # bind the state's layout so folds/refreshes run through the
            # sharded cholupdate — on a copy, so the caller's adaptation
            # object stays reusable with other (e.g. eager) servers
            import copy
            adaptation = copy.copy(adaptation)
            adaptation.dist = self.spec
            adaptation.fifo_n = self.fifo_n
            adaptation._dist_fns = {}
        self.adaptation = adaptation
        self.policy = policy
        self.monitor_drift = bool(monitor_drift)
        self.jitter = float(jitter)
        self.tenants = tenants
        self.clock = clock
        self.registry = registry
        self.tracer = tracer
        self.metrics = ServerMetrics(window=metrics_window,
                                     registry=registry, prefix="serve")
        if registry is not None and tenants is not None \
                and getattr(tenants, "registry", None) is None:
            tenants.registry = registry
        if registry is not None and self.adaptation is not None \
                and getattr(self.adaptation, "registry", None) is None:
            self.adaptation.registry = registry
        # the HealthMonitor rides the adaptation: margins drain and the
        # audit cadence ticks inside maybe_refresh, which the worker runs
        # after every maintenance batch — the probe literally rides the
        # async maintenance queue between microbatches
        self.health = health
        if health is not None and self.adaptation is not None \
                and getattr(self.adaptation, "health", None) is None:
            self.adaptation.health = health
        if tracer is not None and self.adaptation is not None \
                and getattr(self.adaptation, "tracer", None) is None:
            self.adaptation.tracer = tracer
        # optional FlightRecorder: request digests land at the response
        # boundary (_finalize — the worker's only block_until_ready) and
        # the recorder observes at the maintenance boundary, mirroring
        # where the eager server hooks it
        self.recorder = recorder
        self.damping_state = None          # read by the worker's refresh

        self._solve_cache: Dict[tuple, Any] = {}
        self._cv = threading.Condition()
        self._results: Dict[int, SolveResult] = {}
        self._pending: Set[int] = set()
        self._claimed: Set[int] = set()    # uids a result() caller waits on
        self._cancelled: Set[int] = set()
        self._maintenance: List[tuple] = []   # queued apply_fold events
        self._error: Optional[BaseException] = None
        self._stopping = False
        self._drain_on_stop = True
        self._handlers_installed = False
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name="async-solve-server")
        self._worker.start()

    # -- request intake (any thread) ---------------------------------------
    def submit(self, v, *, damping: Optional[float] = None, tokens: int = 1,
               rows=None, payload=None, tenant: Optional[str] = None,
               trace: Optional[str] = None) -> int:
        """Enqueue one request; returns its uid. Thread-safe. ``tenant``
        solves against (and folds ``rows`` into) that tenant's rank-r
        delta — needs a ``TenantManager`` (``tenants=``). ``trace`` tags
        the request's spans with a caller-chosen trace id."""
        if tenant is not None and self.tenants is None:
            raise RuntimeError("tenant= requires a TenantManager "
                               "(AsyncSolveServer(tenants=...))")
        with span("serve.submit", tracer=self.tracer, trace=trace) as sp:
            lam = float(self.state.lam0) if damping is None \
                else float(damping)
            with span("serve.submit.lock", tracer=self.tracer):
                self._cv.acquire()
            try:
                self._raise_if_failed()
                if self._stopping:
                    raise RuntimeError("server is shut down")
                req = self.batcher.submit(v, damping=lam, tokens=tokens,
                                          rows=rows, payload=payload,
                                          tenant=tenant, trace=trace)
                req.t_submit = self.clock()
                if self.registry is not None:
                    qs = self.batcher.queue_stats(req.t_submit)
                    self.registry.gauge("serve.queue_depth").set(qs["depth"])
                    self.registry.gauge("serve.queue_oldest_age_s").set(
                        qs["oldest_age_s"])
                self._pending.add(req.uid)
                self._cv.notify_all()
            finally:
                self._cv.release()
            sp.set(uid=req.uid)
        return req.uid

    def result(self, uid: int, *, timeout: Optional[float] = None
               ) -> SolveResult:
        """Block until request ``uid`` is served and return its result.
        Safe against a concurrent ``flush()``: the uid is claimed first,
        so the flush won't hand it to its own caller."""
        with self._cv:
            self._claimed.add(uid)
            try:
                ok = self._cv.wait_for(
                    lambda: (uid in self._results or uid in self._cancelled
                             or self._error is not None), timeout)
                self._raise_if_failed()
                if not ok:
                    raise TimeoutError(
                        f"request {uid} not served in {timeout}s")
                if uid in self._cancelled:
                    self._cancelled.discard(uid)
                    raise RuntimeError(f"request {uid} was cancelled by a "
                                       "non-draining shutdown")
                return self._results.pop(uid)
            finally:
                self._claimed.discard(uid)

    def apply_fold(self, rows, *, slots=None, record: bool = True) -> int:
        """Enqueue one (possibly remote) fold event for the worker thread
        — the gossip-replay entry point (``repro.fleet``). Thread-safe;
        events apply strictly in submission order, between microbatches,
        through the same ``OnlineAdaptation.fold`` as request-carried
        rows (so sharded windows route through the sharded cholupdate).
        ``flush()`` doubles as the application barrier. Returns the queue
        position."""
        if self.adaptation is None:
            raise RuntimeError("apply_fold needs an OnlineAdaptation")
        with self._cv:
            self._raise_if_failed()
            if self._stopping:
                raise RuntimeError("server is shut down")
            self._maintenance.append((rows, slots, record))
            pos = len(self._maintenance)
            self._cv.notify_all()
        return pos

    def flush(self, *, damping_state=None,
              timeout: Optional[float] = None) -> List[SolveResult]:
        """Block until every request submitted so far is served; return
        all unclaimed results FIFO (uids a concurrent ``result()`` call
        is waiting on are left to that caller). API-compatible with the
        eager ``SolveServer.flush`` (the worker does the solving).

        Note on ``damping_state`` timing under async serving: the worker
        makes its drift-refresh decisions as microbatches are served, so
        a state passed here governs *subsequent* refresh checks — unlike
        the eager server, where flush both solves and refreshes. Assign
        ``server.damping_state`` before submitting to pin the state a
        burst is judged against."""
        if damping_state is not None:
            self.damping_state = damping_state
        with self._cv:
            ok = self._cv.wait_for(
                lambda: self._error is not None
                or (not self._pending and not self._maintenance),
                timeout)
            self._raise_if_failed()
            if not ok:
                raise TimeoutError(
                    f"{len(self._pending)} request(s) / "
                    f"{len(self._maintenance)} fold(s) still pending after "
                    f"{timeout}s")
            out = [self._results.pop(u)
                   for u in sorted(set(self._results) - self._claimed)]
        return out

    # -- lifecycle ---------------------------------------------------------
    def shutdown(self, *, drain: bool = True,
                 timeout: Optional[float] = None) -> None:
        """Stop the worker. ``drain=True`` (default) serves every queued
        request first; ``drain=False`` cancels them."""
        with self._cv:
            self._stopping = True
            self._drain_on_stop = drain
            if not drain:
                for req in self.batcher._queue:
                    self._pending.discard(req.uid)
                    self._cancelled.add(req.uid)
                self.batcher._queue.clear()
                self._maintenance.clear()
            self._cv.notify_all()
        self._worker.join(timeout)
        with self._cv:
            self._raise_if_failed()

    def install_shutdown_handlers(self, *, signals=None) -> None:
        """Drain on process exit: registers an atexit hook and signal
        handlers (default SIGTERM) that run ``shutdown(drain=True)`` —
        queued requests are served, gossiped folds applied, and the
        worker thread joined instead of leaked. Call from the main thread
        (CPython restricts ``signal.signal`` to it); the handler then
        chains to any previously installed handler, or exits 0 — the
        clean-drain contract fleet workers rely on."""
        import atexit
        import signal as _signal
        if self._handlers_installed:
            return
        self._handlers_installed = True
        atexit.register(self._shutdown_quietly)
        for sig in (signals if signals is not None else (_signal.SIGTERM,)):
            prev = _signal.getsignal(sig)

            def _handler(signum, frame, _prev=prev):
                self._shutdown_quietly()
                if callable(_prev) and _prev not in (_signal.SIG_IGN,
                                                     _signal.SIG_DFL):
                    _prev(signum, frame)
                else:
                    raise SystemExit(0)

            _signal.signal(sig, _handler)

    def _shutdown_quietly(self) -> None:
        """Idempotent draining shutdown that never raises (atexit/signal
        context); worker errors were already surfaced to callers."""
        try:
            self.shutdown(drain=True)
        except BaseException:
            pass

    def __enter__(self) -> "AsyncSolveServer":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.shutdown(drain=exc_type is None)

    # -- introspection -----------------------------------------------------
    @property
    def stats(self):
        return self.state.stats

    @property
    def factorization(self):
        """The resident factorization as a first-class solver object."""
        return as_factorization(self.state, jitter=self.jitter)

    def sharded_state(self) -> Optional[ShardedServeState]:
        return None if self.spec is None \
            else ShardedServeState(self.state, self.spec, self.widths,
                                   self.fifo_n)

    def _raise_if_failed(self) -> None:
        if self._error is not None:
            raise RuntimeError("server worker failed") from self._error

    # -- the worker (single consumer; owns all device dispatch) ------------
    def _run(self) -> None:
        try:
            inflight: Optional[Tuple[Microbatch, tuple]] = None
            n_mb = 0
            while True:
                with self._cv:
                    if self._nothing_to_do(inflight):
                        with span("serve.idle", tracer=self.tracer):
                            while self._nothing_to_do(inflight):
                                self._cv.wait()
                    maint = self._maintenance[:]
                    queued = len(self.batcher) > 0
                    stop_now = (self._stopping and not queued and not maint)
                if maint:
                    # gossiped folds apply in order, between microbatches
                    # — same boundary as request-carried rows; the next
                    # dispatch sees the reconciled window
                    for rows, slots, record in maint:
                        self.state = self.adaptation.fold(
                            self.state, rows, slots=slots, record=record)
                    self._maybe_refresh()
                    with self._cv:
                        del self._maintenance[:len(maint)]
                        self._cv.notify_all()
                if queued:
                    n_mb += 1
                    with span("serve.microbatch", tracer=self.tracer,
                              step_num=n_mb) as step:
                        with self._cv, span("serve.form",
                                            tracer=self.tracer) as form:
                            mb = self.batcher.next_microbatch()
                            if mb is not None:
                                form.set(k=mb.k)
                        if mb is not None:      # None: cancelled meanwhile
                            step.set(k=mb.k)
                            inflight = self._serve_microbatch(mb, inflight)
                elif inflight is not None:
                    self._release(self._finalize(*inflight))
                    inflight = None
                elif stop_now:
                    return
        except BaseException as e:           # surfaced on the caller side
            with self._cv:
                self._error = e
                self._cv.notify_all()

    def _nothing_to_do(self, inflight) -> bool:
        return (len(self.batcher) == 0 and not self._stopping
                and inflight is None and not self._maintenance)

    def _serve_microbatch(self, mb: Microbatch, inflight):
        """Dispatch ``mb``; returns the microbatch left in flight."""
        handle = self._dispatch(mb)
        if mb.tenant is not None:
            # tenant-private folds: into the delta, right after the
            # same-microbatch solve dispatched (the eager solve → fold
            # ordering, per tenant)
            for req in mb.requests:
                if req.rows is not None:
                    self.tenants.fold(self.state, mb.tenant, req.rows)
        if self.adaptation is not None:
            # the fold reads state, never the solve's outputs: dispatching
            # it before materializing responses keeps the device stream
            # contiguous while preserving the eager solve → fold → refresh
            # value ordering. Results release only once the refresh
            # decision is in, so flush() doubles as a state-snapshot
            # barrier.
            self._adapt_folds(mb)
            results = self._finalize(mb, handle)
            self._maybe_refresh()
            self._release(results)
            return inflight
        if inflight is not None:
            self._release(self._finalize(*inflight))
        return mb, handle                    # i+1 runs while i lands

    def _sync(self, site: str):
        return host_sync(site, registry=self.registry, tracer=self.tracer)

    def _dispatch(self, mb: Microbatch) -> tuple:
        """Launch the coalesced solve; returns unmaterialized arrays plus
        the dispatch timestamp (the queue-wait / device-solve split)."""
        t_disp = self.clock()
        with span("serve.dispatch", tracer=self.tracer, k=mb.k,
                  uids=[r.uid for r in mb.requests]) as sp:
            x, resid = self._dispatch_arrays(mb, sp)
        return x, resid, t_disp

    def _dispatch_arrays(self, mb: Microbatch, sp) -> tuple:
        st = self.state
        if mb.tenant is not None:
            return self._dispatch_tenant(mb)
        with self._sync("lam0"):
            lam0 = float(st.lam0)
        uniform = all(r.damping == lam0 for r in mb.requests)
        sp.set(uniform=uniform)
        monitor = self.monitor_drift and self.policy == "cached"
        refactorize = self.policy == "refactorize"
        if self.spec is None:
            return _coalesced_solve(
                st.S, st.W, st.L, st.lam0, mb.V, mb.dampings,
                mode=serve_mode(st), jitter=self.jitter, uniform=uniform,
                monitor=monitor, refactorize=refactorize)
        return self._sharded_solve(True if uniform else False, monitor,
                                   refactorize)(
            st.S, st.W, st.L, st.lam0, self._pad_rhs(mb.V), mb.dampings)

    def _sharded_solve(self, uniform: bool, monitor: bool,
                       refactorize: bool):
        key = (uniform, monitor, refactorize)
        fn = self._solve_cache.get(key)
        if fn is None:
            fn = make_sharded_coalesced_solve(
                self.spec, mode=serve_mode(self.state), jitter=self.jitter,
                uniform=uniform, monitor=monitor, refactorize=refactorize)
            self._solve_cache[key] = fn
        return fn

    def _dispatch_tenant(self, mb: Microbatch) -> tuple:
        """A tenant microbatch: the tenant's L_t replaces the resident L
        in whichever solve path (replicated jit / sharded shard_map) the
        layout uses — L was always a replicated argument. Monitoring is
        skipped (the residual is defined against the base system); mixed
        per-request λ solves per-unique-λ groups eagerly, since L_t must
        be rebuilt per λ anyway."""
        st = self.state
        lam0 = float(st.lam0)
        lams = sorted({r.damping for r in mb.requests})
        blocked = isinstance(mb.V, (tuple, list))

        def solve_at(lam: float, V, dampings):
            L_t = self.tenants.factor(
                st, mb.tenant, lam=None if lam == lam0 else lam)
            lam_arr = jnp.asarray(lam, jnp.asarray(st.lam0).dtype)
            if self.spec is None:
                x, _ = _coalesced_solve(
                    st.S, st.W, L_t, lam_arr, V, dampings,
                    mode=serve_mode(st), jitter=self.jitter, uniform=True,
                    monitor=False, refactorize=False)
            else:
                x, _ = self._sharded_solve(True, False, False)(
                    st.S, st.W, L_t, lam_arr, self._pad_rhs(V), dampings)
            return x

        no_resid = -jnp.ones((), jnp.float32)
        if len(lams) == 1:
            return solve_at(lams[0], mb.V, mb.dampings), no_resid
        cols: dict = {}
        for lam in lams:
            idx = [j for j, r in enumerate(mb.requests) if r.damping == lam]
            Vg = tuple(vb[:, idx] for vb in mb.V) if blocked \
                else mb.V[:, idx]
            xg = solve_at(lam, Vg, jnp.full((len(idx),), lam, jnp.float32))
            for a, j in enumerate(idx):
                cols[j] = tuple(xb[:, a] for xb in xg) if blocked \
                    else xg[:, a]
        if blocked:
            x = tuple(jnp.stack([cols[j][b] for j in range(mb.k)], axis=1)
                      for b in range(len(mb.V)))
        else:
            x = jnp.stack([cols[j] for j in range(mb.k)], axis=1)
        return x, no_resid

    def _pad_rhs(self, V):
        """Zero-pad stacked RHS columns to the padded window widths (an
        uneven window carries zero pad columns — exact no-ops)."""
        if self.widths is None:
            return V
        from repro.serve.adapt import pad_to_window_cols
        return pad_to_window_cols(self.state.S, V, axis=0)

    def _unpad_x(self, x):
        """Slice solutions back to the logical parameter count."""
        if self.widths is None:
            return x
        if isinstance(x, (tuple, list)):
            return tuple(xb[:w] for xb, w in zip(x, self.widths))
        return x[:self.widths[0]]

    def _finalize(self, mb: Microbatch, handle: tuple) -> List[SolveResult]:
        """The response boundary: the only block_until_ready."""
        x, resid, t_disp = handle
        x = self._unpad_x(x)
        with span("serve.wait", tracer=self.tracer):
            jax.block_until_ready(x)
        t_done = self.clock()
        with span("serve.results", tracer=self.tracer):
            return self._results_of(mb, x, resid, t_disp, t_done)

    def _results_of(self, mb: Microbatch, x, resid, t_disp: float,
                    t_done: float) -> List[SolveResult]:
        """Per-request slices and records of one materialized microbatch."""
        st = self.state
        stats = st.stats._replace(
            served=st.stats.served + jnp.asarray(mb.k, jnp.int32),
            microbatches=st.stats.microbatches + 1,
            last_residual=jnp.where(resid >= 0, resid,
                                    st.stats.last_residual))
        self.state = st._replace(age=st.age + 1, stats=stats)
        if self.registry is not None:
            self.registry.counter("serve.microbatches").inc()
            self.registry.histogram("serve.solve_latency_s").observe(
                t_done - t_disp)
        results = []
        mb_resid = None
        if self.recorder is not None:
            with self._sync("recorder_resid"):
                mb_resid = float(resid)
        for j, req in enumerate(mb.requests):
            xj = tuple(xb[:, j] for xb in x) \
                if isinstance(x, (tuple, list)) else x[:, j]
            queue_s = max(t_disp - req.t_submit, 0.0) \
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

    def _release(self, results: List[SolveResult]) -> None:
        with span("serve.release", tracer=self.tracer), self._cv:
            for r in results:
                self._results[r.uid] = r
                self._pending.discard(r.uid)
            self._cv.notify_all()

    def _adapt_folds(self, mb: Microbatch) -> None:
        if mb.tenant is not None:
            return          # tenant rows went to the delta, not the window
        for req in mb.requests:
            if req.rows is not None:
                self.state = self.adaptation.fold(self.state, req.rows,
                                                  trace=req.trace)

    def _maybe_refresh(self) -> None:
        self.state, _ = self.adaptation.maybe_refresh(
            self.state, damping_state=self.damping_state)
        if self.registry is not None:
            # age/residual were just pulled to host by the policy check
            # (a refresh replaced both with fresh arrays)
            with self._sync("gauges"):
                age = int(self.state.age)
                resid = float(self.state.stats.last_residual)
            self.registry.gauge("curvature.factor_age").set(age)
            self.registry.gauge("curvature.last_drift_residual").set(resid)
        if self.recorder is not None:
            # maintenance boundary == the eager server's flush end: the
            # policy check just synchronized, so the recorder tick (and
            # its cadenced fingerprint) adds no new device round trip
            self.recorder.observe(self.state, adaptation=self.adaptation,
                                  health=self.health,
                                  registry=self.registry,
                                  tracer=self.tracer)
