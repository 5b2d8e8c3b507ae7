"""End-to-end trainer CLI — config → mesh → data → optimizer → supervised
loop with checkpoint/restart.

    PYTHONPATH=src python -m repro.launch.trainer --arch llama3-8b \
        --optimizer ngd --steps 200 --batch 8 --seq 128 \
        --mesh-shape 1,1 --smoke

``--smoke`` selects the reduced config (CPU-runnable); the full configs are
exercised via the dry-run. ``--optimizer ngd`` is the paper's damped
natural gradient (Algorithm 1) end to end.
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint import checkpoint as ckpt
from repro.data import SyntheticLM, place
from repro.launch import train as T
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.supervisor import SupervisorConfig, run_supervised
from repro.models.api import get_api
from repro.optim import AdamW, NaturalGradient, warmup_cosine

__all__ = ["train_main", "build_trainer", "build_server", "build_fleet",
           "ServeHandles"]


def build_trainer(cfg, *, mesh, optimizer_name: str, lr: float,
                  damping: float, batch: int, seq: int, total_steps: int,
                  solver: str = "chol", momentum: float = 0.9,
                  score_chunk=None, blocked: bool = False,
                  curvature: str = "exact", curvature_refresh: int = 10,
                  curvature_drift_tol=None, curvature_drift_frac=None,
                  seed: int = 0):
    """Returns (init_state, step_fn, save_state, restore_state, data).

    ``blocked``: NGD keeps S as per-layer BlockedScores blocks — no flat
    (n, m) score buffer is ever materialized (the paper-scale memory
    ceiling of the dense path).

    ``curvature``: "exact" re-solves the damped Fisher from scratch every
    step (the paper; unchanged default); "streaming" carries the n×n Gram
    across steps with a full refresh every ``curvature_refresh`` steps
    (and on residual drift past ``curvature_drift_tol`` — or, when
    ``curvature_drift_frac`` is set instead, past the threshold autotuned
    from the damping schedule's trust-region ratio; the static tol
    overrides the autotune) — the O(n²·m) pass is skipped on cache-hit
    steps."""
    api = get_api(cfg)
    data = SyntheticLM(cfg, batch=batch, seq=seq, seed=seed)
    sched = warmup_cosine(lr, warmup_steps=max(total_steps // 20, 1),
                          total_steps=total_steps)

    if curvature not in ("exact", "streaming", None):
        raise ValueError(f"unknown curvature mode {curvature!r}")
    if curvature == "streaming":
        if optimizer_name != "ngd":
            raise ValueError(
                "curvature='streaming' maintains the NGD damped-Fisher "
                f"factorization; it has no meaning for {optimizer_name!r}")
        if solver != "chol":
            raise ValueError(
                "curvature='streaming' replaces the Cholesky dual solve "
                f"and cannot honor solver={solver!r}; use solver='chol' "
                "or curvature='exact'")

    if optimizer_name == "ngd":
        if curvature == "streaming":
            from repro.curvature import StreamingCurvature
            policy = StreamingCurvature(batch,
                                        refresh_every=curvature_refresh,
                                        drift_tol=curvature_drift_tol,
                                        drift_frac=curvature_drift_frac)
        else:
            policy = None
        opt = NaturalGradient(sched, damping=damping, solver=solver,
                              momentum=momentum, curvature=policy)
    else:
        opt = AdamW(sched)

    sample = data.batch_at(0)
    specs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), sample)
    pspecs = api.param_specs()
    if optimizer_name == "ngd":
        jstep, (pshard, oshard, ishard) = T.jit_ngd_train_step(
            api, opt, mesh, param_specs=pspecs, input_specs=specs,
            score_chunk=score_chunk, blocked=blocked)
    else:
        jstep, (pshard, oshard, ishard) = T.jit_train_step(
            api, opt, mesh, param_specs=pspecs, input_specs=specs)

    def init_state():
        params = jax.device_put(api.init_params(jax.random.key(seed)),
                                pshard)
        opt_state = jax.device_put(opt.init(params), oshard)
        return {"params": params, "opt": opt_state}

    def step_fn(state, step):
        batch_np = data.batch_at(step)
        b = place(batch_np, ishard)
        params, opt_state, metrics = jstep(state["params"], state["opt"], b)
        return {"params": params, "opt": opt_state}, metrics

    step_fn.jitted = jstep        # benchmarks introspect compiled memory
    step_fn.shardings = (pshard, oshard, ishard)

    def save_state(d, step, state):
        ckpt.save(d, step, state, metadata={"arch": cfg.name})

    def restore_state(d, step):
        like = jax.eval_shape(init_state)
        shards = {"params": pshard, "opt": oshard}
        state, _ = ckpt.restore(d, step, like, shardings=shards)
        return state

    return init_state, step_fn, save_state, restore_state, data


class ServeHandles:
    """Everything the serving loop needs besides the ``SolveServer``:
    the model api, live params, the jitted score-grad step for adaptation
    batches, a decoder factory over the serve steps, the data source
    seeding synthetic traffic, and the parameter unravel for applying
    flat natural-gradient updates."""

    def __init__(self, *, api, params, data, score_grads, unravel, mesh):
        self.api = api
        self.params = params
        self.data = data
        self.score_grads = score_grads     # (params, batch) -> (loss, v, S)
        self.unravel = unravel             # flat (m,) -> params-shaped tree
        self.mesh = mesh
        self._decoders = {}                # (b, plen, new) -> jitted step

    def apply_update(self, x_flat, *, lr: float):
        """θ ← θ − lr·x for a flat natural-gradient solve result.

        ``x`` is gathered to host first: a sharded server returns it laid
        out over the model axis, and folding that placement into the
        replicated live params would commit them to mismatched shardings.
        """
        delta = self.unravel(jnp.asarray(np.asarray(x_flat)))
        self.params = jax.tree.map(
            lambda p, d: (p - lr * d.astype(p.dtype)).astype(p.dtype),
            self.params, delta)
        return self.params

    def decode(self, prompt, *, new_tokens: int):
        """Prefill + greedy one-token decode of ``prompt`` (b, T) through
        the jitted serve steps (``launch.train.jit_prefill`` /
        ``jit_serve_step``); returns (b, new_tokens) generated ids."""
        prompt = jnp.asarray(prompt, jnp.int32)
        b, plen = prompt.shape
        max_len = plen + new_tokens
        logits, cache, _ = self.api.prefill(
            self.params, {"tokens": prompt, "max_len": max_len})
        ispecs = {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32),
                  "cache": jax.eval_shape(lambda: cache),
                  "cache_index": jax.ShapeDtypeStruct((), jnp.int32)}
        key = (b, plen, new_tokens)
        if key not in self._decoders:
            self._decoders[key] = T.jit_serve_step(
                self.api, self.mesh,
                param_specs=jax.eval_shape(lambda: self.params),
                input_specs=ispecs, donate=False)[0]
        step = self._decoders[key]
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        out = [tok]
        for t in range(new_tokens - 1):
            nxt, cache = step(self.params, cache, jnp.asarray(plen + t),
                              out[-1])
            out.append(nxt[:, None])
        return jnp.concatenate(out, axis=1)


def _build_serve_front(cfg, *, mesh, window: int, seq: int,
                       score_chunk=None, seed: int = 0):
    """The model-side half of serving: api + params + jitted score-grad
    pass + seeded window. Shared by ``build_server`` (which pairs it with
    an in-process solve server) and ``build_fleet`` (which ships the
    window to worker processes and keeps only the traffic-side model)."""
    from jax.flatten_util import ravel_pytree

    api = get_api(cfg)
    data = SyntheticLM(cfg, batch=window, seq=seq, seed=seed)
    params = api.init_params(jax.random.key(seed))
    _, unravel = ravel_pytree(params)

    sample = data.batch_at(0)
    specs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), sample)
    pspecs = api.param_specs()
    # request rows carry the window's 1/√n normalization so folds are
    # exchangeable with the seeded rows
    jscore, _ = T.jit_score_grads(api, mesh, param_specs=pspecs,
                                  input_specs=specs, score_chunk=score_chunk,
                                  scale=1.0 / np.sqrt(window))
    _, _, S0 = jscore(params, sample)
    handles = ServeHandles(api=api, params=params, data=data,
                           score_grads=jscore, unravel=unravel, mesh=mesh)
    return handles, S0


def build_server(cfg, *, mesh, window: int, seq: int, damping: float = 1e-3,
                 max_tokens: int = 4096, max_requests: int = 8,
                 refresh_every: int = 64, drift_tol=None, drift_frac=0.25,
                 jitter: float = 0.0, score_chunk=None, policy: str = "cached",
                 layout=None, async_: bool = False, oversize: str = "split",
                 window_dtype=None, tenant_rank=None, tenant_budget_mb=None,
                 seed: int = 0, audit_every: int = 0, audit_probes: int = 2,
                 registry=None, tracer=None, profile=None, health=None,
                 recorder=None, record_dir=None):
    """Config → mesh → model → resident curvature window → server.

    The serving twin of ``build_trainer``: builds the jitted serve steps
    (prefill + one-token decode from ``launch.train``, plus the score-grad
    pass for adaptation batches), seeds an n=``window`` sample score
    window from synthetic data, factorizes it once, and wraps it in a
    request-driven server with token-budget batching and the age/drift
    online-adaptation policy. Returns ``(server, handles)``.

    ``async_=True`` returns the concurrent ``repro.dist.AsyncSolveServer``
    (thread-safe submits, device/host overlap) instead of the eager
    ``SolveServer``; ``layout`` ("1d" | "2d") additionally shards the
    resident window over ``mesh`` per ``repro.dist.DistSpec`` — the
    request path and the adaptation folds then run through the shard_map
    solve and the distributed cholupdate. A sharded window requires the
    async server (the eager one is the replicated baseline).

    ``window_dtype`` (e.g. "bfloat16"): low-precision storage for the
    resident score window — halves window HBM bytes; every S pass still
    accumulates fp32 (see ``init_serve_state``).

    ``tenant_rank`` (int): attach a ``repro.tenants.TenantManager`` so
    ``submit(..., tenant=...)`` serves per-tenant rank-r deltas over the
    shared base factor; ``tenant_budget_mb`` caps resident tenant bytes
    (LRU spill past it).

    ``registry`` / ``tracer`` / ``profile`` (``repro.obs``): thread the
    observability fabric through the server — mergeable metrics, per-
    request spans, optional ``jax.profiler`` capture around the solve.
    ``health`` (``repro.obs.HealthMonitor``) attaches the numerical-health
    rule engine; ``audit_every`` runs the ``curvature.audit`` condest +
    residual probe every that many maintenance passes (0: off).

    ``recorder`` (``repro.obs.FlightRecorder``) attaches the flight
    recorder — per-request digests, cadenced state fingerprints, and
    automatic incident bundles on health-verdict escalations;
    ``record_dir`` is the shorthand that constructs one rooted there.
    """
    from repro.serve import (OnlineAdaptation, SolveServer,
                             TokenBudgetBatcher, init_serve_state)

    handles, S0 = _build_serve_front(cfg, mesh=mesh, window=window, seq=seq,
                                     score_chunk=score_chunk, seed=seed)
    if recorder is None and record_dir is not None:
        from repro.obs import FlightRecorder
        recorder = FlightRecorder(str(record_dir))
    adaptation = OnlineAdaptation(refresh_every=refresh_every,
                                  drift_tol=drift_tol, drift_frac=drift_frac,
                                  jitter=jitter, audit_every=audit_every,
                                  audit_probes=audit_probes)
    batcher = TokenBudgetBatcher(max_tokens=max_tokens,
                                 max_requests=max_requests,
                                 oversize=oversize)
    tenants = None
    if tenant_rank is not None:
        from repro.tenants import TenantManager
        tenants = TenantManager(
            int(tenant_rank),
            budget_bytes=None if tenant_budget_mb is None
            else int(float(tenant_budget_mb) * 2**20),
            registry=registry)
    if layout is not None and not async_:
        raise ValueError(
            f"layout={layout!r} shards the resident window, which only the "
            "async server serves; pass async_=True (the eager SolveServer "
            "is the replicated baseline)")
    if async_:
        from repro.dist import (AsyncSolveServer, DistSpec,
                                init_sharded_serve_state)
        state = init_serve_state(S0, damping, jitter=jitter,
                                 window_dtype=window_dtype) \
            if layout is None else init_sharded_serve_state(
                S0, damping, spec=DistSpec(mesh, layout), jitter=jitter,
                window_dtype=window_dtype)
        server = AsyncSolveServer(state, batcher=batcher,
                                  adaptation=adaptation, policy=policy,
                                  jitter=jitter, tenants=tenants,
                                  registry=registry, tracer=tracer,
                                  profile=profile, health=health,
                                  recorder=recorder)
    else:
        server = SolveServer(init_serve_state(S0, damping, jitter=jitter,
                                              window_dtype=window_dtype),
                             batcher=batcher, adaptation=adaptation,
                             policy=policy, jitter=jitter, tenants=tenants,
                             registry=registry, tracer=tracer,
                             profile=profile, health=health,
                             recorder=recorder)
    return server, handles


def build_fleet(cfg, *, mesh, n_workers: int = 2, route: str = "round_robin",
                reconcile: bool = True, window: int, seq: int,
                damping: float = 1e-3, max_tokens: int = 4096,
                max_requests: int = 8, refresh_every: int = 64,
                drift_tol=None, drift_frac=0.25, jitter: float = 0.0,
                score_chunk=None, policy: str = "cached",
                async_workers: bool = False, worker_layout=None,
                window_dtype=None, tenant_rank=None, tenant_budget_mb=None,
                seed: int = 0, trace: bool = False, registry=None,
                audit_every: int = 0, profile_dir=None, record_dir=None):
    """Config → model → seeded window → N-process serving fleet.

    The fleet twin of ``build_server``: the model (score-grad pass,
    decode, live params) stays on this side as the traffic source, while
    the resident curvature window is shipped — as bytes, over the init
    frame — to ``n_workers`` local worker processes that each factorize
    the *identical* window (the precondition for gossip convergence).
    Returns ``(dispatcher, handles)``; drive it exactly like a server
    (``submit``/``flush``), plus ``reconcile()``/``probe()``/
    ``checkpoint()``.

    ``route``: "round_robin" | "least_loaded" | "by_adapter" (pass
    ``adapter=`` at submit for sticky routing). ``reconcile=True`` gossips
    every request's fold columns fleet-wide through the dispatcher's
    ``GossipLog`` so all windows converge; ``False`` partitions folds —
    each worker's window sees only its own requests' rows (meaningful
    under ``by_adapter``, where each adapter's curvature then lives on
    its sticky worker). ``async_workers``/``worker_layout`` select the
    inner server flavour each worker wraps (eager replicated by default;
    async; async + window sharded over the worker's own devices).

    ``tenant_rank``/``tenant_budget_mb``: give every worker a
    ``TenantManager`` so ``submit(..., tenant=...)`` rides the
    consistent-hash ``by_adapter`` ring as tenant placement (each
    tenant's delta + journal lives on exactly one worker).

    ``trace=True`` turns on per-request span tracing in every worker —
    spans ride result frames back and land in ``dispatcher.tracer``, so
    ``dispatcher.tracer.export(path)`` yields one cross-process Chrome
    trace. ``registry``: dispatcher-side ``repro.obs.MetricsRegistry``
    (routing latency under the ``fleet.*`` prefix); worker registries are
    always on and merge via ``dispatcher.fleet_metrics()``.

    ``audit_every``: each worker runs the ``curvature.audit`` condest +
    residual probe every that many maintenance passes (0: off); per-
    worker health verdicts ride heartbeat pongs and merge via
    ``dispatcher.fleet_health()``. ``profile_dir``: each worker captures
    a ``jax.profiler`` trace into ``<dir>/worker<i>/``. ``record_dir``:
    each worker runs a flight recorder rooted at ``<dir>/worker<i>/`` —
    incident bundle paths ride pongs and are gathered by
    ``dispatcher.collect_incidents()``.
    """
    from repro.fleet import launch_fleet
    from repro.fleet.wire import put_blocks

    handles, S0 = _build_serve_front(cfg, mesh=mesh, window=window, seq=seq,
                                     score_chunk=score_chunk, seed=seed)
    meta = {"mode": "inline", "damping": float(damping),
            "jitter": float(jitter), "policy": policy,
            "max_tokens": int(max_tokens), "max_requests": int(max_requests),
            "refresh_every": int(refresh_every), "drift_tol": drift_tol,
            "drift_frac": drift_frac, "async": bool(async_workers),
            "layout": worker_layout,
            "window_dtype": None if window_dtype is None
            else str(jnp.dtype(window_dtype)),
            "tenant_rank": None if tenant_rank is None else int(tenant_rank),
            "tenant_budget_mb": tenant_budget_mb,
            "obs": True, "trace": bool(trace),
            "audit_every": int(audit_every),
            "profile_dir": None if profile_dir is None else str(profile_dir),
            "record_dir": None if record_dir is None else str(record_dir)}
    arrays = {}
    from repro.core.operator import is_blocked
    put_blocks(arrays, meta, "S0",
               tuple(S0.blocks) if is_blocked(S0) else S0)
    dispatcher = launch_fleet(n_workers, init_meta=meta, init_arrays=arrays,
                              route=route, gossip=reconcile,
                              registry=registry)
    return dispatcher, handles


def train_main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.list_archs(), required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--optimizer", choices=["adamw", "ngd"], default="adamw")
    ap.add_argument("--solver", default="chol",
                    choices=["chol", "eigh", "svd", "cg"])
    ap.add_argument("--blocked", action="store_true",
                    help="per-layer BlockedScores NGD path (no flat S)")
    ap.add_argument("--curvature", choices=["exact", "streaming"],
                    default="exact",
                    help="per-step exact factorization (paper) or the "
                         "cross-step streaming curvature cache")
    ap.add_argument("--curvature-refresh", type=int, default=10,
                    help="streaming: full Gram refresh period (steps)")
    ap.add_argument("--curvature-drift-tol", type=float, default=None,
                    help="streaming: refresh when the solve's relative "
                         "residual exceeds this (static; overrides "
                         "--curvature-drift-frac)")
    ap.add_argument("--curvature-drift-frac", type=float, default=None,
                    help="streaming: autotune the drift threshold as this "
                         "fraction of the damping schedule's trust-region "
                         "ratio (repro.core.auto_drift_tol)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--damping", type=float, default=1e-3)
    ap.add_argument("--mesh-shape", default="1,1")
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--inject-failure-at", type=int, default=None)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get_config(args.arch)
    shape = tuple(int(x) for x in args.mesh_shape.split(","))
    axes = ("data", "model")[:len(shape)] if len(shape) <= 2 \
        else ("pod", "data", "model")
    mesh = make_mesh(shape, axes)
    lr = args.lr if args.lr is not None else \
        (0.05 if args.optimizer == "ngd" else 3e-3)

    init_state, step_fn, save_state, restore_state, _ = build_trainer(
        cfg, mesh=mesh, optimizer_name=args.optimizer, lr=lr,
        damping=args.damping, batch=args.batch, seq=args.seq,
        total_steps=args.steps, solver=args.solver, blocked=args.blocked,
        curvature=args.curvature, curvature_refresh=args.curvature_refresh,
        curvature_drift_tol=args.curvature_drift_tol,
        curvature_drift_frac=args.curvature_drift_frac)

    losses = []

    def logging_step(state, step):
        t0 = time.time()
        state, metrics = step_fn(state, step)
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % args.log_every == 0:
            print(f"step {step:5d} loss {loss:8.4f} "
                  f"({(time.time() - t0) * 1e3:.0f} ms)", flush=True)
        return state, metrics

    sup = SupervisorConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                           ckpt_every=args.ckpt_every,
                           inject_failure_at=args.inject_failure_at)
    state, report = run_supervised(sup, init_state=init_state,
                                   step_fn=logging_step,
                                   save_state=save_state,
                                   restore_state=restore_state)
    print(f"done: final loss {losses[-1]:.4f} "
          f"(first {losses[0]:.4f}); report={report}")
    return losses, report


if __name__ == "__main__":
    train_main()
