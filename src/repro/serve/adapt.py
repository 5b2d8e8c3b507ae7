"""Online adaptation — serving gradients folded into the resident window.

Every adaptation request carries per-sample score rows of its examples
(scaled by the window's 1/√n — see ``per_sample_scores(scale=...)``).
After its solve completes, those rows enter the resident n-sample window
FIFO, k oldest samples retiring per fold, through the sliding-sample-
window algebra of ``repro.curvature.update``:

    cols = S·rows†  (one O(n·m·k) pass — the *only* m-sized work)
    X, Y, W' = replace_factors(W, cols, idx)          (2k×2k core split)
    L' = chol_downdate(chol_update(L, X), Y)          (O(n²·k))
    S'[idx] = rows

so the factor tracks the fine-tuned weights at O(n·m·k) per fold — never
the O(n²·m) Gram, never an O(n³) refactorization, on the request path.

Staleness is bounded exactly like the training-side ``CurvatureCache``:
``maybe_refresh`` (called by the server *between* microbatches) triggers
a full refactorization when the factor's age exceeds ``refresh_every``
microbatches or the last monitored solve residual exceeds the drift
threshold — static ``drift_tol`` if set, else the ``drift_frac``
autotune against the damping schedule (``repro.core.auto_drift_tol``).

Folds are also *events*: with a ``journal`` attached (or an ``on_fold``
callback) every applied fold is emitted as a ``FoldEvent`` — the rows
plus the FIFO slots they landed in — and ``fold(..., slots=...)`` replays
such an event, verifying the slots against the local FIFO cursor so a
replica ingesting a remote log (``repro.fleet``) can only apply it in
order. Replaying the same events onto the same initial state reproduces
the origin's factor bit for bit (``FoldJournal.replay``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.damping import auto_drift_tol
from repro.core.operator import BlockedScores, is_blocked
from repro.core.solvers import chol_factorize
from repro.curvature.update import chol_downdate, chol_update, replace_factors
from repro.kernels import ops as kernel_ops
from repro.obs.trace import host_sync, span
from repro.serve.state import ServeState, serve_mode

__all__ = ["OnlineAdaptation", "pad_to_window_cols"]


def pad_to_window_cols(S, values, *, axis: int, cast: Optional[bool] = None):
    """Zero-pad ``values`` (dense array or per-block tuple) along ``axis``
    up to the resident window's column widths — the single place the
    pad-to-mesh rule is applied to incoming data. A sharded window may
    carry zero pad columns (``repro.dist`` uneven-shard support); zeros
    are exact no-ops in every S pass, so fold rows (axis=1: (k, m)) and
    stacked RHS (axis=0: (m, k)) pad here and stay exact.

    ``cast`` (default: ``axis == 1``, i.e. fold rows): additionally round
    the values to each window block's storage dtype — the ONE dtype-aware
    cast point shared by ``OnlineAdaptation.fold`` and
    ``sharded_window_cols``. A bf16 window then computes its fold cross
    columns from exactly the values the FIFO write will store (no silent
    per-call-site upcasts, no W-vs-S drift); RHS columns (axis=0) are
    *not* rounded — solve accumulation stays fp32."""
    S_blocks = S.blocks if is_blocked(S) else (S,)
    val_blocks = tuple(values) if isinstance(values, (tuple, list)) \
        else (values,)
    if cast is None:
        cast = axis == 1

    def pad(v, block):
        if cast and v.dtype != block.dtype \
                and jnp.issubdtype(block.dtype, jnp.floating) \
                and jnp.issubdtype(v.dtype, jnp.floating):
            v = v.astype(block.dtype)
        width = block.shape[1]
        if v.shape[axis] >= width:
            return v
        spec = [(0, 0)] * v.ndim
        spec[axis] = (0, width - v.shape[axis])
        return jnp.pad(v, spec)

    padded = tuple(pad(v, b) for b, v in zip(S_blocks, val_blocks))
    if isinstance(values, (tuple, list)):
        return padded
    return padded[0]


@functools.partial(jax.jit, static_argnames=("mode", "with_aux"))
def _fold_window(S, W, L, slot, rows, *, mode, with_aux=False):
    """One FIFO fold: rows (k, m) dense or tuple of per-block (k, m_b)
    pieces replace the k oldest window samples; returns (S', W', L',
    slot') — plus the downdate's ``DowndateAux`` when ``with_aux`` (the
    breakdown margin stays an unmaterialized device scalar until a host
    sync site reads it). Pure and jitted — the fold is
    request-path-adjacent work."""
    n = W.shape[0]
    blocked = isinstance(S, BlockedScores)
    row_blocks = tuple(rows) if isinstance(rows, (tuple, list)) else (rows,)
    k = row_blocks[0].shape[0]
    idx = (slot + jnp.arange(k, dtype=jnp.int32)) % n

    # new Gram columns W'[:, idx]: inner products of the post-replacement
    # window with the incoming rows — old rows via one S·rows† pass, the
    # replaced rows' own entries via the small rows·rows† corner. Both run
    # in the fused fold kernel on TPU (one pass, resident accumulators),
    # the identical-algebra jnp reference elsewhere.
    S_blocks = S.blocks if blocked else (S,)
    cols, corner = kernel_ops.fold_cols(S, rows)
    acc = jnp.promote_types(W.dtype, jnp.float32)
    cols = cols.astype(acc)
    corner = corner.astype(acc)
    cols = cols.at[idx, :].set(corner)

    X, Y, Wp = replace_factors(W, cols, idx)
    aux = None
    if with_aux:
        Lp, aux = chol_downdate(chol_update(L, X), Y, return_aux=True)
    else:
        Lp = chol_downdate(chol_update(L, X), Y)
    new_blocks = tuple(b.at[idx, :].set(r.astype(b.dtype))
                       for b, r in zip(S_blocks, row_blocks))
    Sp = BlockedScores(new_blocks, names=S.names) if blocked \
        else new_blocks[0]
    if with_aux:
        return Sp, Wp, Lp, (slot + k) % n, aux
    return Sp, Wp, Lp, (slot + k) % n


class OnlineAdaptation:
    """Bounded-staleness maintenance policy for the serving window.

    Thresholds mirror ``repro.curvature.StreamingCurvature`` (age period +
    drift bound, with the static ``drift_tol`` overriding the
    ``drift_frac`` autotune); ``from_policy`` copies them from a training-
    side policy so serving and training share one staleness contract.
    """

    def __init__(self, *, refresh_every: int = 64,
                 drift_tol: Optional[float] = None,
                 drift_frac: Optional[float] = 0.25,
                 jitter: float = 0.0, dist=None, journal=None,
                 on_fold=None, registry=None, health=None,
                 audit_every: int = 0, audit_probes: int = 2,
                 condest_iters: int = 2):
        if refresh_every < 1:
            raise ValueError("refresh_every must be >= 1")
        self.refresh_every = int(refresh_every)
        # optional repro.obs.MetricsRegistry: fold/refresh rates and
        # window-bytes health series (all python-side — no device syncs
        # beyond the ones the staleness policy already does)
        self.registry = registry
        # optional repro.obs.HealthMonitor: receives fold-row rejection
        # events and is re-evaluated at every maybe_refresh boundary
        self.health = health
        # factor audit cadence (condest + Hutchinson residual probe),
        # counted in maybe_refresh calls (one per microbatch boundary);
        # 0 disables. The audit and the downdate margins both materialize
        # at the maybe_refresh host sync the staleness policy already
        # pays for — no new device round trips on the request path.
        self.audit_every = int(audit_every)
        self.audit_probes = int(audit_probes)
        self.condest_iters = int(condest_iters)
        self._audit_tick = 0
        self._audit_step = 0
        self._audit_fn = None
        # unmaterialized DowndateAux scalars from recent folds, drained
        # (host-read) at the next maybe_refresh; bounded so a caller that
        # never reaches maybe_refresh can't grow it without limit
        self._pending_aux: list = []
        self.drift_tol = None if drift_tol is None else float(drift_tol)
        self.drift_frac = None if drift_frac is None else float(drift_frac)
        self.jitter = float(jitter)
        # optional repro.dist.DistSpec: folds and refreshes then run
        # through the sharded cholupdate (per-slab psums, replicated
        # factor) instead of the single-device jit
        self.dist = dist
        # FIFO modulus override: an uneven 2d window stores zero-padded
        # sample rows, but the FIFO must cycle over the *logical* n so
        # pad rows stay zero (set by the async server when it binds a
        # padded ShardedServeState; None: W's size is the modulus)
        self.fifo_n = None
        # optional serve.journal.FoldJournal: every applied fold/refresh
        # is recorded as a replayable event; on_fold(event) additionally
        # fires per fold (the fleet tier's gossip emission hook)
        self.journal = journal
        self.on_fold = on_fold
        self._dist_fns = {}            # (kind, mode) -> jitted shard_map fn
        # optional repro.obs.Tracer, set by the server that holds this
        # policy: the adapt.* spans then also become Chrome events
        self.tracer = None

    @classmethod
    def from_policy(cls, policy, *, jitter: Optional[float] = None
                    ) -> "OnlineAdaptation":
        """Adopt a ``StreamingCurvature`` policy's thresholds."""
        return cls(refresh_every=policy.refresh_every,
                   drift_tol=policy.drift_tol,
                   drift_frac=getattr(policy, "drift_frac", None),
                   jitter=policy.jitter if jitter is None else jitter)

    def effective_drift_tol(self, damping_state=None):
        if self.drift_tol is not None:
            return jnp.asarray(self.drift_tol, jnp.float32)
        if self.drift_frac is not None:
            return auto_drift_tol(damping_state, frac=self.drift_frac)
        return None

    def fold(self, state: ServeState, rows, *, slots=None,
             record: bool = True, trace: Optional[str] = None
             ) -> ServeState:
        """Fold one request's score rows into the window (FIFO replace).

        ``rows``: (k, m) dense — or a tuple of per-block (k, m_b) pieces
        matching a blocked window. Requires k ≤ n (a single request never
        displaces more than the whole window).

        ``slots``: optional explicit FIFO slot indices from a replayed
        ``FoldEvent``. The fold always lands at the local cursor — slots
        are *verified* against it (raising on divergence) so a gossip
        replayer can only apply a log in its recorded order, which is
        what makes replay bit-identical to the origin.

        ``record=False`` suppresses journal/on_fold emission (used by the
        replayer itself so ingested events aren't re-logged as local).
        ``trace`` tags the ``adapt.fold`` span with a request's trace id.
        """
        row_blocks = tuple(rows) if isinstance(rows, (tuple, list)) \
            else (rows,)
        k = int(row_blocks[0].shape[0])
        with span("adapt.fold", tracer=self.tracer, trace=trace, rows=k):
            return self._fold(state, rows, row_blocks, k, slots, record)

    def _fold(self, state: ServeState, rows, row_blocks, k: int, slots,
              record: bool) -> ServeState:
        n = self.fifo_n if self.fifo_n is not None \
            else int(state.W.shape[0])
        if k > n:
            raise ValueError(f"cannot fold {k} rows into an n={n} window")
        if is_blocked(state.S) and len(row_blocks) != len(state.S.blocks):
            raise ValueError(
                f"{len(row_blocks)} row blocks for a "
                f"{len(state.S.blocks)}-block window")
        emit = record and (self.journal is not None
                           or self.on_fold is not None)
        if slots is not None or emit:
            # host-side cursor read: only when an event identity is needed
            with self._sync("fold_cursor"):
                cursor = int(state.slot)
            expect = tuple((cursor + i) % n for i in range(k))
            if slots is not None and tuple(int(s) for s in slots) != expect:
                raise ValueError(
                    f"fold replay out of order: event slots "
                    f"{tuple(int(s) for s in slots)} vs local FIFO cursor "
                    f"{expect} (apply events in journal order)")
        rows_in = rows if isinstance(rows, (tuple, list)) \
            else jnp.asarray(rows)
        # the one dtype-aware cast + pad point: rows are rounded to the
        # window storage dtype here, so journal/gossip, the cols pass and
        # the FIFO write all see the same stored values
        rows_in = pad_to_window_cols(state.S, rows_in, axis=1)
        if not self._rows_finite(rows_in):
            # a single NaN/Inf row would poison W, L and the FIFO slab at
            # once — reject the fold (deterministic everywhere, so gossip
            # replicas reject the same event) and surface it instead
            if self.registry is not None:
                self.registry.counter("serve.fold.rejected_nonfinite").inc()
            if self.health is not None:
                import time as _time

                from repro.obs.health import HealthEvent
                self.health.record_event(HealthEvent(
                    ts=_time.time(), severity="degraded",
                    rule="nonfinite_folds",
                    series="serve.fold.rejected_nonfinite",
                    value=1.0, bound=0.0,
                    recommendation="fold rows with NaN/Inf were rejected: "
                                   "check the score producer upstream"))
            return state
        track_aux = self.registry is not None and self.dist is None
        if self.dist is not None:
            fold = self._dist_fn("fold", serve_mode(state))
            Sp, Wp, Lp, slot = fold(state.S, state.W, state.L, state.slot,
                                    rows_in)
        elif track_aux:
            Sp, Wp, Lp, slot, aux = _fold_window(
                state.S, state.W, state.L, state.slot, rows_in,
                mode=serve_mode(state), with_aux=True)
            if len(self._pending_aux) < 1024:
                self._pending_aux.append(aux)
        else:
            Sp, Wp, Lp, slot = _fold_window(
                state.S, state.W, state.L, state.slot, rows_in,
                mode=serve_mode(state))
        stats = state.stats._replace(
            adapted=state.stats.adapted + jnp.asarray(k, jnp.int32))
        if self.registry is not None:
            self.registry.counter("curvature.folds").inc()
            self.registry.counter("curvature.fold_rows").inc(k)
            self._window_gauges(Sp)
        if emit:
            ev = None
            if self.journal is not None:
                ev = self.journal.append_fold(expect, rows_in)
            if self.on_fold is not None:
                if ev is None:
                    from repro.serve.journal import FoldEvent
                    ev = FoldEvent(seq=-1, kind="fold", slots=expect,
                                   rows=rows_in)
                self.on_fold(ev)
        return state._replace(S=Sp, W=Wp, L=Lp, slot=slot, stats=stats)

    def _sync(self, site: str):
        return host_sync(site, registry=self.registry, tracer=self.tracer)

    def _rows_finite(self, rows_in) -> bool:
        """One fused isfinite reduction over the (already device-resident)
        fold rows. The host read rides the same boundary as the journal's
        cursor read — a scalar pull, not a data transfer."""
        blocks = tuple(rows_in) if isinstance(rows_in, (tuple, list)) \
            else (rows_in,)
        ok = jnp.asarray(True)
        for b in blocks:
            ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(b)))
        with self._sync("rows_finite"):
            return bool(ok)

    def _window_gauges(self, S) -> None:
        """Window storage by dtype — shape/dtype metadata only, no device
        reads (``nbytes`` on a committed jax array is static)."""
        blocks = S.blocks if is_blocked(S) else (S,)
        by_dtype: dict = {}
        for b in blocks:
            name = jnp.dtype(b.dtype).name
            by_dtype[name] = by_dtype.get(name, 0) + int(b.nbytes)
        for name, nb in by_dtype.items():
            self.registry.gauge(f"window.bytes.{name}").set(nb)

    def _dist_fn(self, kind: str, mode: str):
        """Build-once cache of the sharded fold/refresh for ``self.dist``."""
        fn = self._dist_fns.get((kind, mode))
        if fn is None:
            from repro.dist.cholupdate import (make_sharded_fold,
                                               make_sharded_refresh)
            spec = self.dist
            if kind == "fold":
                fn = make_sharded_fold(
                    spec.mesh, layout=spec.layout,
                    model_axis=spec.model_axis, data_axis=spec.data_axis,
                    mode=mode, fifo_n=self.fifo_n)
            else:
                fn = make_sharded_refresh(
                    spec.mesh, layout=spec.layout,
                    model_axis=spec.model_axis, data_axis=spec.data_axis,
                    mode=mode, jitter=self.jitter)
            self._dist_fns[(kind, mode)] = fn
        return fn

    def maybe_refresh(self, state: ServeState, *, damping_state=None,
                      force: bool = False, record: bool = True
                      ) -> Tuple[ServeState, bool]:
        """Full W refactorization when the staleness bound is hit — called
        between microbatches, never on the request path. Returns
        (state', refreshed)."""
        with span("adapt.policy", tracer=self.tracer) as policy:
            reason = self._refresh_reason(state, damping_state, force)
            policy.set(reason=reason)
        refreshed = reason != "none"
        if refreshed:
            with span("adapt.refresh", tracer=self.tracer, reason=reason):
                state = self._refresh(state, reason, record)
        # we are at the maintenance host-sync boundary anyway — drain the
        # pending downdate margins, run the periodic factor audit, and
        # let the health rules look at the fresh numbers
        with span("adapt.health", tracer=self.tracer):
            self._observe_health(state)
        return state, refreshed

    def _refresh_reason(self, state: ServeState, damping_state,
                        force: bool) -> str:
        """"force", "age", "drift" or "none": the staleness decision."""
        tol = self.effective_drift_tol(damping_state)
        with self._sync("last_residual"):
            r = float(state.stats.last_residual)
        with self._sync("age"):
            age_due = int(state.age) >= self.refresh_every
        drift_due = False
        if tol is not None and r >= 0.0:
            with self._sync("drift_tol"):
                drift_due = r > float(tol)
        if force:
            return "force"
        return "age" if age_due else "drift" if drift_due else "none"

    def _refresh(self, state: ServeState, reason: str,
                 record: bool) -> ServeState:
        if record and self.journal is not None:
            self.journal.append_refresh()
        if self.dist is not None:
            W, L = self._dist_fn("refresh", serve_mode(state))(
                state.S, state.lam0)
        else:
            fac = chol_factorize(state.S, state.lam0,
                                 mode=serve_mode(state),
                                 jitter=self.jitter)
            W, L = fac.W, fac.L
        stats = state.stats._replace(
            refreshes=state.stats.refreshes + 1,
            last_residual=-jnp.ones((), jnp.float32))
        if self.registry is not None:
            self.registry.counter("curvature.refreshes").inc()
            self.registry.counter(f"curvature.refresh_{reason}").inc()
        return state._replace(W=W, L=L, age=jnp.zeros((), jnp.int32),
                              stats=stats)

    def _observe_health(self, state: ServeState) -> None:
        """Materialize pending downdate margins + run the audit cadence.

        Called from ``maybe_refresh`` (already a host-sync site). The
        fleet-facing gauges: ``curvature.downdate_margin`` (worst margin
        since last drain — min-merged across workers),
        ``curvature.downdate_clamped`` (count of clamped sweeps),
        ``curvature.condest`` and ``curvature.factor_residual`` from the
        periodic audit.
        """
        if self.registry is None:
            self._pending_aux.clear()
            return
        if self._pending_aux:
            # drain only folds whose device computation already finished:
            # blocking here would serialize the in-flight fold chain
            # against the next microbatch's host-side batching. The folds
            # execute in order, so stop at the first unready one; a
            # backlog past 64 force-drains, bounding the gauge's lag.
            pending = self._pending_aux
            split = len(pending)
            if split <= 64:
                for i, a in enumerate(pending):
                    ready = getattr(a.margin, "is_ready", None)
                    if ready is not None and not ready():
                        split = i
                        break
            done, self._pending_aux = pending[:split], pending[split:]
            margins, clamped = [], 0
            for a in done:
                with self._sync("margin"):
                    margins.append(float(a.margin))
                    clamped += bool(a.clamped)
            vals = [v for v in margins if v == v]      # NaN-proof min
            if vals:
                self.registry.gauge(
                    "curvature.downdate_margin").set(min(vals))
            if clamped:
                self.registry.counter(
                    "curvature.downdate_clamped").inc(clamped)
        if self.audit_every > 0:
            self._audit_tick += 1
            if self._audit_tick >= self.audit_every:
                self._audit_tick = 0
                with span("adapt.audit", tracer=self.tracer):
                    self.audit(state)
        if self.health is not None:
            self.health.evaluate()

    def audit(self, state: ServeState) -> dict:
        """One explicit factor audit: Hager/Higham 1-norm condition
        estimate of W + λĨ plus a Hutchinson probe of the factor
        residual — a handful of O(n²) solves/matvecs against the
        *resident* W and L, no refactorization. Mirrors the results into
        ``curvature.condest`` / ``curvature.factor_residual`` and
        returns them as floats.
        """
        from repro.curvature.audit import audit_factor
        if self._audit_fn is None:
            self._audit_fn = jax.jit(functools.partial(
                audit_factor, iters=self.condest_iters,
                probes=self.audit_probes))
        self._audit_step += 1
        res = self._audit_fn(state.W, state.L, state.lam0,
                             step=self._audit_step)
        with self._sync("audit"):
            out = {"condest": float(res.condest),
                   "residual": float(res.residual)}
        if self.registry is not None:
            self.registry.gauge("curvature.condest").set(out["condest"])
            self.registry.gauge(
                "curvature.factor_residual").set(out["residual"])
        return out
