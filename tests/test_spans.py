"""The serving path's spans on the profiler's clock (``repro.obs.span``).

A tiny ``AsyncSolveServer`` with adaptation, folds, audits and a drift
refresh on every monitored microbatch runs under ``jax.profiler``; the
capture's ``.xplane.pb`` must hold every span the worker and the callers
write, with their arguments, nested as the worker loop nests them, and
one ``host.sync`` per count on the registry's ``serve.host_syncs``.
Without a capture or a tracer the answers stay bit-identical to the
eager server's.
"""
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.dist import AsyncSolveServer
from repro.obs import MetricsRegistry, Tracer, span
from repro.obs.trace import _arg
from repro.serve import (OnlineAdaptation, SolveServer, TokenBudgetBatcher,
                         init_serve_state)

N, M = 12, 160
SERVE_SPANS = {"serve.submit", "serve.submit.lock", "serve.idle",
               "serve.microbatch", "serve.form", "serve.dispatch",
               "adapt.fold", "serve.wait", "serve.results", "adapt.policy",
               "adapt.refresh", "adapt.health", "adapt.audit",
               "serve.release", "host.sync"}


def _window(seed=3):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(N, M)) / np.sqrt(M), jnp.float32)


def _requests(seed, count):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(count):
        v = jnp.asarray(rng.normal(size=(M,)), jnp.float32)
        rows = jnp.asarray(rng.normal(size=(2, M)) / np.sqrt(M),
                           jnp.float32) if i % 3 == 1 else None
        out.append((v, 0.4 if i % 8 == 5 else None, rows))
    return out


def _adaptation():
    # drift_tol=0: every monitored (uniform) microbatch reads a residual
    # above it, so the policy refreshes for drift
    return OnlineAdaptation(refresh_every=10 ** 6, drift_tol=0.0,
                            audit_every=2)


def _burst(srv, reqs):
    """Queue ``reqs`` as one burst (the worker forms wide microbatches)
    and wait for every answer."""
    with srv._cv:
        uids = [srv.submit(v, damping=lam, rows=rows)
                for v, lam, rows in reqs]
    return [srv.result(u, timeout=120) for u in uids]


def _host_spans(path):
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for i, ln in enumerate(plane.lines):
                for ev in ln.events:
                    if ev.name.startswith(("serve.", "adapt.", "host.")):
                        out.append((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    {k: v for k, v in ev.stats}, i))
    return out


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """Spans of two bursts traced after an untraced warm-up burst, and the
    registry's host-sync count over the same stretch."""
    reg = MetricsRegistry()
    srv = AsyncSolveServer(init_serve_state(_window(), 0.1),
                           batcher=TokenBudgetBatcher(max_requests=4),
                           adaptation=_adaptation(), registry=reg)
    try:
        _burst(srv, _requests(0, 8))             # compiles every program
        before = reg.snapshot()["counters"].get("serve.host_syncs", 0)
        log_dir = tmp_path_factory.mktemp("prof")
        jax.profiler.start_trace(str(log_dir))
        try:
            _burst(srv, _requests(1, 8))
            time.sleep(0.05)                     # the worker idles
            _burst(srv, _requests(2, 8))
            time.sleep(0.05)
            after = reg.snapshot()["counters"]["serve.host_syncs"]
        finally:
            jax.profiler.stop_trace()
    finally:
        srv.shutdown()
    path, = glob.glob(str(log_dir / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    return _host_spans(path), after - before


def test_every_span_of_the_serving_path_is_captured(captured):
    spans, _ = captured
    assert SERVE_SPANS <= {s[0] for s in spans}


def test_dispatch_uids_round_trip_space_joined(captured):
    spans, _ = captured
    submitted = {int(a["uid"]) for n, _, _, a, _ in spans
                 if n == "serve.submit"}
    dispatched = []
    for n, _, _, a, _ in spans:
        if n == "serve.dispatch":
            ids = [int(u) for u in str(a["uids"]).split()]
            assert len(ids) == a["k"]
            dispatched += ids
    assert any(a["k"] > 1 for n, _, _, a, _ in spans
               if n == "serve.dispatch")
    assert sorted(dispatched) == sorted(submitted) and len(submitted) == 16


def test_refresh_and_policy_carry_their_reason(captured):
    spans, _ = captured
    refresh = [a for n, _, _, a, _ in spans if n == "adapt.refresh"]
    assert refresh and all(a["reason"] == "drift" for a in refresh)
    reasons = [a["reason"] for n, _, _, a, _ in spans if n == "adapt.policy"]
    assert set(reasons) <= {"none", "drift"}
    assert reasons.count("drift") == len(refresh)


def test_worker_spans_nest_in_their_microbatch(captured):
    spans, _ = captured
    steps = [s for s in spans if s[0] == "serve.microbatch"]
    assert steps and all("step_num" in s[3] and s[3]["k"] >= 1
                         for s in steps)
    for name in ("serve.form", "serve.dispatch", "serve.wait",
                 "serve.results", "adapt.policy", "serve.release"):
        inner = [s for s in spans if s[0] == name]
        assert len(inner) == len(steps), name
        for _, s0, s1, _, line in inner:
            assert any(m[4] == line and m[1] <= s0 and s1 <= m[2]
                       for m in steps), name
    # the callers' spans are on another thread than the worker's
    assert {s[4] for s in spans if s[0] == "serve.submit"}.isdisjoint(
        {m[4] for m in steps})


def test_host_sync_spans_match_the_counter(captured):
    spans, counted = captured
    syncs = [a["site"] for n, _, _, a, _ in spans if n == "host.sync"]
    assert counted > 0 and len(syncs) == counted
    assert {"lam0", "rows_finite", "last_residual", "age", "drift_tol",
            "margin", "audit", "gauges"} <= set(syncs)


def test_answers_without_capture_or_tracer_match_the_eager_server():
    reqs = _requests(4, 9)

    def drive(server, submit_all):
        uids = [server.submit(v, damping=lam, rows=rows)
                for v, lam, rows in reqs]
        got = {r.uid: np.asarray(r.x) for r in submit_all(server)}
        return [got[u] for u in uids]

    def serve(cls):
        return cls(init_serve_state(_window(), 0.1),
                   batcher=TokenBudgetBatcher(max_requests=1),
                   adaptation=_adaptation(), registry=MetricsRegistry())

    eager = serve(SolveServer)
    ref = drive(eager, lambda s: s.flush())
    srv = serve(AsyncSolveServer)
    try:
        got = drive(srv, lambda s: s.flush(timeout=120))
    finally:
        srv.shutdown()
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a, b)
    assert int(srv.stats.refreshes) == int(eager.stats.refreshes) > 0


def test_span_arguments_and_chrome_events():
    assert _arg([3, 4, 5]) == "3 4 5"
    assert _arg("a,b#c=d") == "a b c d"
    assert _arg(7) == 7 and _arg(True) is True
    t = Tracer(pid=1)
    t0 = time.time() * 1e6
    with span("serve.dispatch", tracer=t, trace="req1", k=2,
              uids=[1, 2]) as sp:
        sp.set(uniform=True)
    with span("host.sync", site="x"):        # no tracer: profiler only
        pass
    ev, = t.events()
    assert ev["name"] == "serve.dispatch" and ev["cat"] == "serve"
    assert ev["args"] == {"k": 2, "uids": [1, 2], "uniform": True,
                          "trace": "req1"}
    # the Chrome timestamp is the span's perf_counter reading on the
    # epoch clock, not a second reading of time.time()
    assert abs(ev["ts"] - t0) < 5e6 and ev["dur"] >= 0
