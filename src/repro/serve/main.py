"""``serve_main`` — the online NGD serving loop as a CLI.

    PYTHONPATH=src python -m repro.serve --arch llama3.2-3b --smoke \
        --requests 12 --window 8 --seq 16 --decode-tokens 4

Synthetic request traffic drives the full serving path end to end: each
request carries a handful of fine-tuning examples and a prompt. Per
request the loop

1. runs the jitted score-grad pass (``launch.train.jit_score_grads``) —
   mean-gradient RHS v plus per-sample score rows for the window fold;
2. submits v to the token-budget batcher with the request's λ;
3. flushes coalesced microbatches through the ``SolveServer`` (resident
   factor; no Gram on the request path), applies the natural-gradient
   updates to the live params, and lets ``OnlineAdaptation`` fold the
   rows / trigger age+drift refreshes (threshold autotuned from the
   damping schedule via the Levenberg–Marquardt gain ratio);
4. greedy-decodes the response through the jitted serve steps.

``ServeState`` and the params checkpoint every ``--ckpt-every``
microbatch rounds through ``repro.checkpoint`` (atomic, resumable).
Prints p50/p99 solve latency, requests/sec and cache counters at exit.

``--tenants N`` drives a multi-tenant trace: each request carries a
zipf-distributed tenant id and its rows fold into that tenant's rank-r
delta (``repro.tenants``) instead of the shared base window; combine
with ``--fleet K --route by_adapter`` so the consistent-hash ring pins
each tenant to one worker. Tenant packing stats print at exit.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint import checkpoint as ckpt
from repro.core.damping import DampingState, LevenbergMarquardtDamping
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.trainer import build_server

__all__ = ["serve_main"]


def serve_main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--arch", choices=configs.list_archs(),
                    default="llama3.2-3b")
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="reduced config (CPU-runnable); on by default")
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--requests", type=int, default=12,
                    help="synthetic requests to serve")
    ap.add_argument("--window", type=int, default=8,
                    help="resident curvature window size n (samples)")
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--adapt-examples", type=int, default=2,
                    help="fine-tuning examples per request")
    ap.add_argument("--decode-tokens", type=int, default=4,
                    help="greedy tokens decoded per request (0: skip)")
    ap.add_argument("--damping", type=float, default=1e-2,
                    help="resident λ0; requests may deviate per-request")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--max-tokens", type=int, default=64,
                    help="batcher token budget per microbatch")
    ap.add_argument("--max-requests", type=int, default=4,
                    help="batcher RHS width cap per microbatch")
    ap.add_argument("--burst", type=int, default=3,
                    help="requests submitted before each flush (lets the "
                         "batcher actually coalesce)")
    ap.add_argument("--refresh-every", type=int, default=16,
                    help="age bound: full refresh after this many "
                         "microbatches")
    ap.add_argument("--drift-tol", type=float, default=None,
                    help="static drift bound (overrides --drift-frac)")
    ap.add_argument("--drift-frac", type=float, default=0.25,
                    help="autotuned drift bound fraction "
                         "(repro.core.auto_drift_tol)")
    ap.add_argument("--mesh-shape", default="1,1")
    ap.add_argument("--mesh", choices=["replicated", "1d", "2d"],
                    default="replicated",
                    help="window layout: replicated (eager-compatible), or "
                         "sharded over the mesh per repro.dist.DistSpec "
                         "(1d: params on the model axis; 2d: samples x "
                         "params). Sharded layouts imply --async.")
    ap.add_argument("--async", dest="async_", action="store_true",
                    help="serve through repro.dist.AsyncSolveServer: "
                         "thread-safe submits, the device executes the "
                         "previous coalesced solve while the host batches "
                         "the next")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="serve through N worker processes behind the "
                         "repro.fleet.Dispatcher (0: in-process server)")
    ap.add_argument("--route", choices=["round_robin", "least_loaded",
                                        "by_adapter"],
                    default="round_robin",
                    help="fleet routing policy (--fleet)")
    ap.add_argument("--no-reconcile", action="store_true",
                    help="fleet: do not gossip window folds between "
                         "workers — folds partition by routed worker "
                         "(meaningful with --route by_adapter)")
    ap.add_argument("--tenants", type=int, default=0, metavar="N",
                    help="multi-tenant trace: requests carry zipf-"
                         "distributed tenant ids over N tenants; each "
                         "tenant's rows fold into its own rank-r delta "
                         "over the shared base factor (0: off)")
    ap.add_argument("--tenant-rank", type=int, default=4,
                    help="per-tenant delta rank budget r (--tenants)")
    ap.add_argument("--tenant-budget-mb", type=float, default=None,
                    help="resident tenant byte budget in MiB; LRU spill "
                         "past it (--tenants; default: unbounded)")
    ap.add_argument("--window-dtype", choices=["fp32", "bf16"],
                    default="fp32",
                    help="resident score-window storage dtype: bf16 halves "
                         "window bytes; Gram/solve arithmetic stays fp32")
    ap.add_argument("--ckpt-dir", default="artifacts/serve_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=8,
                    help="checkpoint cadence in flush rounds (0: off)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve live metrics over HTTP on this port "
                         "(/metrics Prometheus text, /metrics.json raw "
                         "snapshot; 0: ephemeral port). The fleet endpoint "
                         "merges worker snapshots into one view.")
    ap.add_argument("--metrics-snapshot", default=None, metavar="PATH",
                    help="write the (fleet-merged) metrics snapshot JSON "
                         "here at checkpoint cadence and at exit")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable per-request span tracing and export a "
                         "Chrome-trace JSON here at exit (--fleet: spans "
                         "from every worker stitch into one file)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the coalesced "
                         "solves into DIR (--fleet: each worker writes "
                         "its own trace under DIR/worker<i>/)")
    ap.add_argument("--audit-every", type=int, default=4, metavar="K",
                    help="run the curvature.audit condition estimate + "
                         "Hutchinson factor-residual probe every K "
                         "maintenance passes (0: off)")
    ap.add_argument("--health-port", type=int, default=None, metavar="PORT",
                    help="bind an extra HTTP endpoint serving the "
                         "numerical-health report at /health (0: ephemeral "
                         "port). /health also rides --metrics-port.")
    ap.add_argument("--record-dir", default=None, metavar="DIR",
                    help="run the flight recorder: bounded in-memory "
                         "request digests + journal tail + state "
                         "fingerprints, flushed to atomic incident bundles "
                         "under DIR on health-verdict escalations (replay "
                         "offline with python -m repro.obs.forensics; "
                         "--fleet: each worker records under "
                         "DIR/worker<i>/)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get_config(args.arch)
    args.window_dtype = \
        None if args.window_dtype == "fp32" else "bfloat16"
    shape = tuple(int(x) for x in args.mesh_shape.split(","))
    axes = ("data", "model")[:len(shape)] if len(shape) <= 2 \
        else ("pod", "data", "model")
    mesh = make_mesh(shape, axes)

    if args.fleet:
        return _serve_fleet(args, cfg, mesh)

    layout = None if args.mesh == "replicated" else args.mesh
    async_ = args.async_ or layout is not None

    from repro.obs import HealthMonitor, MetricsRegistry, ProfileHooks, \
        Tracer
    registry = MetricsRegistry()
    health = HealthMonitor(registry)
    tracer = Tracer() if args.trace_out else None
    profile = ProfileHooks(args.profile_dir) if args.profile_dir else None
    if profile is not None:
        profile.start()
    recorder = None
    if args.record_dir:
        from repro.obs import FlightRecorder
        recorder = FlightRecorder(args.record_dir)
        # unclean-death coverage: a degraded/critical process that dies
        # without flushing still leaves a final bundle behind
        recorder.install_exit_capture()

    t0 = time.perf_counter()
    server, h = build_server(
        cfg, mesh=mesh, window=args.window, seq=args.seq,
        damping=args.damping, max_tokens=args.max_tokens,
        max_requests=args.max_requests, refresh_every=args.refresh_every,
        drift_tol=args.drift_tol, drift_frac=args.drift_frac,
        layout=layout, async_=async_, window_dtype=args.window_dtype,
        tenant_rank=args.tenant_rank if args.tenants else None,
        tenant_budget_mb=args.tenant_budget_mb, seed=args.seed,
        audit_every=args.audit_every,
        registry=registry, tracer=tracer, profile=profile, health=health,
        recorder=recorder)
    endpoint_port = _start_endpoint(args, registry, health=health.report)
    kind = f"async {layout or 'replicated'}" if async_ else "eager"
    print(f"resident window factorized: n={args.window} "
          f"m={server.state.S.shape[1]} λ0={args.damping} [{kind}] "
          f"({(time.perf_counter() - t0) * 1e3:.0f} ms)", flush=True)

    lm = LevenbergMarquardtDamping(args.damping)
    dstate: DampingState = lm.init()
    rng = np.random.default_rng(args.seed)
    losses, rounds = [], 0
    pending = {}      # uid -> (v, loss_before, batch)

    for r in range(args.requests):
        if async_:
            # the async worker serves (and drift-checks) microbatches as
            # they arrive — pin the damping state before submitting, not
            # at flush time
            server.damping_state = dstate
        # one synthetic request: adaptation examples + a prompt
        full = h.data.batch_at(r + 1)
        take = rng.choice(args.window, size=args.adapt_examples,
                          replace=False)
        ex = jax.tree.map(lambda x: x[np.sort(take)], full)
        loss, v, rows = h.score_grads(h.params, ex)
        # per-request λ: occasional requests ask for extra damping
        lam = args.damping * (4.0 if r % 5 == 4 else 1.0)
        # zipf tenant traffic: a few hot tenants, a long cold tail
        tenant = f"t{(int(rng.zipf(1.5)) - 1) % args.tenants}" \
            if args.tenants else None
        uid = server.submit(v, damping=lam,
                            tokens=args.adapt_examples * args.seq, rows=rows,
                            tenant=tenant)
        pending[uid] = (v, float(loss), ex)

        if (r + 1) % args.burst and r != args.requests - 1:
            continue
        results = server.flush(damping_state=dstate)
        for res in results:
            v_req, loss_before, ex_req = pending.pop(res.uid)
            h.apply_update(res.x, lr=args.lr)
            # trust-region feedback for the drift autotune: actual vs
            # predicted reduction of this request's adaptation loss
            loss_after, _, _ = h.score_grads(h.params, ex_req)
            predicted = args.lr * float(jnp.vdot(v_req, res.x).real)
            dstate = lm.update(dstate,
                               actual_reduction=loss_before
                               - float(loss_after),
                               predicted_reduction=max(predicted, 1e-30))
            losses.append(loss_before)
            if args.decode_tokens > 0:
                prompt = jnp.asarray(ex_req["inputs"][:1, :args.seq])
                gen = h.decode(prompt, new_tokens=args.decode_tokens)
                ids = np.asarray(gen[0])
                print(f"req {res.uid:3d} λ={res.damping:.3g} "
                      f"loss {loss_before:8.4f} "
                      f"solve {res.latency_s * 1e3:6.1f} ms "
                      f"tokens {ids[:8].tolist()}", flush=True)
        if results:
            rounds += 1
            if args.ckpt_every and rounds % args.ckpt_every == 0:
                ckpt.save(args.ckpt_dir, rounds,
                          {"serve": server.state, "params": h.params},
                          metadata={"arch": cfg.name})
                if args.metrics_snapshot:
                    from repro.obs import write_snapshot
                    write_snapshot(args.metrics_snapshot,
                                   registry.snapshot(),
                                   health=health.report())

    s = server.metrics.summary()
    st = server.stats
    print(f"served {s['served']} requests: "
          f"p50 {s['p50_ms']:.1f} ms  p99 {s['p99_ms']:.1f} ms  "
          f"{s['rps']:.1f} req/s  {s['tokens_per_s']:.0f} tok/s")
    rep = health.report()
    print(f"health: {rep['verdict']} "
          f"(active: {sorted(rep['active']) or 'none'})")
    print(f"window: adapted {int(st.adapted)} rows, "
          f"{int(st.refreshes)} full refreshes over "
          f"{int(st.microbatches)} microbatches "
          f"(drift tol now "
          f"{float(server.adaptation.effective_drift_tol(dstate)):.3g}, "
          f"λ now {float(dstate.lam):.3g})")
    if args.tenants and server.tenants is not None:
        p = server.tenants.packing_stats()
        budget = "" if p["budget_bytes"] is None \
            else f" / {p['budget_bytes']} budget"
        print(f"tenants: {p['tenants']} seen, {p['resident']} resident "
              f"({p['resident_bytes']} B{budget}), "
              f"{p['evictions']} evictions, {p['activations']} activations, "
              f"{p['factor_hits']} factor hits / "
              f"{p['materializations']} builds; hot {p['hot']}")
    if args.ckpt_every and rounds:
        ckpt.save(args.ckpt_dir, rounds,
                  {"serve": server.state, "params": h.params},
                  metadata={"arch": cfg.name})
        print(f"checkpointed ServeState+params at round {rounds} "
              f"-> {args.ckpt_dir}")
    if profile is not None:
        profile.stop()
    if recorder is not None:
        nb = len(recorder.bundle_paths)
        print(f"flight recorder: {nb} incident bundle(s)"
              + (f", last {recorder.bundle_paths[-1]}" if nb else "")
              + f" ({recorder.debounced} debounced)")
    _finish_obs(args, registry.snapshot(), tracer=tracer,
                port=endpoint_port, health=True,
                health_report=health.report())
    if async_:
        server.shutdown()
    return server, losses


def _start_endpoint(args, registry, extra_snapshots=None, health=None):
    """``--metrics-port`` / ``--health-port``: bind the stdlib HTTP
    exposition endpoint(s); ``health`` (a zero-arg callable returning the
    health report dict) is served at ``/health`` on each."""
    port = None
    if args.metrics_port is not None:
        from repro.obs import start_metrics_server
        _, port = start_metrics_server(registry, port=args.metrics_port,
                                       extra_snapshots=extra_snapshots,
                                       health=health)
        print(f"metrics endpoint: http://127.0.0.1:{port}/metrics",
              flush=True)
    if args.health_port is not None and args.health_port != port:
        from repro.obs import start_metrics_server
        _, hport = start_metrics_server(registry, port=args.health_port,
                                        extra_snapshots=extra_snapshots,
                                        health=health)
        print(f"health endpoint: http://127.0.0.1:{hport}/health",
              flush=True)
    return port


def _finish_obs(args, snapshot, *, tracer=None, port=None, health=False,
                health_report=None):
    """Exit-time observability: final snapshot file (with the structured
    health report embedded when given), Chrome-trace export, and a
    self-scrape of the live endpoint (proves the exposition path end to
    end — CI asserts on the printed series count)."""
    if args.metrics_snapshot:
        from repro.obs import write_snapshot
        write_snapshot(args.metrics_snapshot, snapshot,
                       health=health_report)
        print(f"metrics snapshot -> {args.metrics_snapshot}")
    if tracer is not None and args.trace_out:
        n = tracer.export(args.trace_out)
        print(f"trace: {n} spans -> {args.trace_out}")
    if port is not None:
        import urllib.request
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        series = [ln for ln in body.splitlines()
                  if ln and not ln.startswith("#")]
        print(f"metrics scrape: {len(series)} series from :{port}")
        if health:
            # self-scrape of the live /health route: proves the verdict
            # path end to end — CI asserts on this line
            import json
            rep = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/health", timeout=10).read())
            print(f"health scrape: verdict={rep['verdict']} "
                  f"active={sorted(rep.get('active', {})) or 'none'}")


def _serve_fleet(args, cfg, mesh):
    """The serving loop against a multi-process fleet: the model (score
    pass + decode + live params) stays here as the traffic source; solves
    and window maintenance happen in the worker processes, folds
    reconciled through the dispatcher's gossip log. ``--async`` /
    ``--mesh 1d|2d`` select each worker's inner server flavour (the
    fleet tier composes with the dist tier: every worker then shards its
    replica over its own devices)."""
    from repro.launch.trainer import build_fleet
    from repro.obs import MetricsRegistry

    registry = MetricsRegistry()
    worker_layout = None if args.mesh == "replicated" else args.mesh
    t0 = time.perf_counter()
    dispatcher, h = build_fleet(
        cfg, mesh=mesh, n_workers=args.fleet, route=args.route,
        reconcile=not args.no_reconcile, window=args.window, seq=args.seq,
        damping=args.damping, max_tokens=args.max_tokens,
        max_requests=args.max_requests, refresh_every=args.refresh_every,
        drift_tol=args.drift_tol, drift_frac=args.drift_frac,
        async_workers=args.async_ or worker_layout is not None,
        worker_layout=worker_layout, window_dtype=args.window_dtype,
        tenant_rank=args.tenant_rank if args.tenants else None,
        tenant_budget_mb=args.tenant_budget_mb, seed=args.seed,
        trace=bool(args.trace_out), registry=registry,
        audit_every=args.audit_every, profile_dir=args.profile_dir,
        record_dir=args.record_dir)
    # the endpoint folds the workers' last-pong snapshots into every
    # response — one scrape sees the whole fleet. /health merges the
    # last-seen pong verdicts (refresh=False: the HTTP thread must not
    # pump the dispatcher's channels under the serving loop)
    endpoint_port = _start_endpoint(
        args, registry,
        extra_snapshots=lambda: [w.metrics for w in dispatcher.workers
                                 if w.metrics],
        health=lambda: dispatcher.fleet_health(refresh=False))
    print(f"fleet up: {args.fleet} workers, route={args.route}, "
          f"reconcile={not args.no_reconcile}, n={args.window} "
          f"({(time.perf_counter() - t0) * 1e3:.0f} ms)", flush=True)

    rng = np.random.default_rng(args.seed)
    losses, rounds = [], 0
    pending = {}
    try:
        for r in range(args.requests):
            full = h.data.batch_at(r + 1)
            take = rng.choice(args.window, size=args.adapt_examples,
                              replace=False)
            ex = jax.tree.map(lambda x: x[np.sort(take)], full)
            loss, v, rows = h.score_grads(h.params, ex)
            lam = args.damping * (4.0 if r % 5 == 4 else 1.0)
            tenant = f"t{(int(rng.zipf(1.5)) - 1) % args.tenants}" \
                if args.tenants else None
            uid = dispatcher.submit(
                np.asarray(v), damping=lam,
                tokens=args.adapt_examples * args.seq,
                rows=np.asarray(rows), tenant=tenant,
                adapter=tenant if tenant is not None else f"user{r % 4}")
            pending[uid] = (float(loss), ex)

            if (r + 1) % args.burst and r != args.requests - 1:
                continue
            results = dispatcher.flush()
            for res in results:
                loss_before, ex_req = pending.pop(res.uid)
                h.apply_update(res.x, lr=args.lr)
                losses.append(loss_before)
                if args.decode_tokens > 0:
                    prompt = jnp.asarray(ex_req["inputs"][:1, :args.seq])
                    gen = h.decode(prompt, new_tokens=args.decode_tokens)
                    ids = np.asarray(gen[0])
                    print(f"req {res.uid:3d} λ={res.damping:.3g} "
                          f"loss {loss_before:8.4f} "
                          f"solve {res.latency_s * 1e3:6.1f} ms "
                          f"tokens {ids[:8].tolist()}", flush=True)
            if results:
                rounds += 1
                if args.ckpt_every and rounds % args.ckpt_every == 0:
                    dispatcher.checkpoint(args.ckpt_dir, rounds)
                    if args.metrics_snapshot:
                        from repro.obs import write_snapshot
                        write_snapshot(
                            args.metrics_snapshot,
                            dispatcher.fleet_metrics(),
                            health=dispatcher.fleet_health(refresh=False))

        dispatcher.reconcile()
        if not args.no_reconcile and len(dispatcher.workers) > 1:
            m = int(np.asarray(v).shape[0])
            probe = dispatcher.probe(
                rng.normal(size=(m,)).astype(np.float32))
            xs = [np.asarray(x) for x in probe.values()]
            worst = max(np.linalg.norm(a - xs[0])
                        / max(np.linalg.norm(xs[0]), 1e-30) for a in xs[1:])
            print(f"reconciled probe agreement across "
                  f"{len(xs)} workers: max rel diff {worst:.2e}")
        s = dispatcher.metrics.summary()
        print(f"served {s['served']} requests: "
              f"p50 {s['p50_ms']:.1f} ms  p99 {s['p99_ms']:.1f} ms  "
              f"{s['rps']:.1f} req/s")
        fh = dispatcher.fleet_health()
        print(f"fleet health: {fh['verdict']} ({fh['members']} members, "
              f"active: {sorted(fh['active']) or 'none'})")
        for wid, rep in sorted(dispatcher.heartbeat().items()):
            line = (f"  worker {wid}: served {rep['served']}, "
                    f"applied {rep['applied']} fold events")
            tp = rep.get("tenants") or {}
            if tp:
                line += (f"; tenants {tp.get('tenants', 0)} "
                         f"({tp.get('resident', 0)} resident, "
                         f"{tp.get('spilled', 0)} spilled), "
                         f"hot {tp.get('hot', {})}")
            print(line)
        if args.record_dir:
            incidents = dispatcher.collect_incidents(refresh=False)
            nb = sum(len(v) for v in incidents.values())
            print(f"flight recorder: {nb} incident bundle(s) across "
                  f"{len(incidents)} worker(s)")
            for wid, paths in sorted(incidents.items()):
                for p in paths:
                    print(f"  worker {wid}: {p}")
        if args.ckpt_every and rounds:
            path = dispatcher.checkpoint(args.ckpt_dir, rounds)
            print(f"fleet checkpoint (per-worker ServeState + manifest) "
                  f"-> {path}")
        _finish_obs(args, dispatcher.fleet_metrics(),
                    tracer=dispatcher.tracer, port=endpoint_port,
                    health=True,
                    health_report=dispatcher.fleet_health(refresh=False))
    finally:
        dispatcher.shutdown()
    return dispatcher, losses


if __name__ == "__main__":
    serve_main()
