"""Observability fabric: mergeable metrics (merged-histogram percentiles
match the single-process union within one bucket width), bounded server
latency ring + queue-wait recording, span tracing with Chrome-trace
export, Prometheus text exposition + live HTTP scrape, and the serving
stack's instrumentation (server / adaptation / tenants emit the series
the fleet view merges).
"""
import json
import urllib.request

import numpy as np
import pytest

from repro.obs import (
    MetricsRegistry,
    ProfileHooks,
    Tracer,
    default_buckets,
    merge,
    prometheus_text,
    quantile,
    start_metrics_server,
    write_snapshot,
)

jnp = pytest.importorskip("jax.numpy")

from repro.serve import (  # noqa: E402
    OnlineAdaptation,
    SolveServer,
    TokenBudgetBatcher,
    init_serve_state,
)
from repro.serve.server import ServerMetrics  # noqa: E402


def _window(n=8, m=64, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, m)) / np.sqrt(m), jnp.float32)


# ---------------------------------------------------------------------------
# registry + merge semantics
# ---------------------------------------------------------------------------

def test_registry_instruments_and_snapshot_roundtrip():
    reg = MetricsRegistry()
    reg.counter("a.b").inc()
    reg.counter("a.b").inc(4)
    reg.gauge("q.depth").set(3)
    reg.histogram("lat").observe(2e-6)
    snap = reg.snapshot()
    assert snap["counters"]["a.b"] == 5
    assert snap["gauges"]["q.depth"] == 3.0
    h = snap["histograms"]["lat"]
    assert h["count"] == 1 and sum(h["counts"]) == 1
    assert len(h["counts"]) == len(h["bounds"]) + 1
    # snapshot is wire-safe plain python (json round-trips exactly)
    assert json.loads(json.dumps(snap)) == snap


def test_merged_histogram_percentiles_match_union():
    """The satellite acceptance check: two workers that each saw half the
    traffic merge to the same p50/p99 (within one factor-2 bucket width)
    as one process that saw all of it."""
    rng = np.random.default_rng(0)
    lat = rng.lognormal(mean=-6.0, sigma=1.5, size=4000)  # ~ms-scale, heavy tail
    a, b = MetricsRegistry(), MetricsRegistry()
    union = MetricsRegistry()
    for i, v in enumerate(lat):
        (a if i % 2 else b).histogram("serve.request_latency_s").observe(v)
        union.histogram("serve.request_latency_s").observe(v)
    merged = merge([a.snapshot(), b.snapshot()])
    hm = merged["histograms"]["serve.request_latency_s"]
    hu = union.snapshot()["histograms"]["serve.request_latency_s"]
    # same fixed buckets -> merged counts are exact, not approximate
    # (sum differs only by float addition order)
    assert hm["bounds"] == hu["bounds"]
    assert hm["counts"] == hu["counts"]
    assert hm["count"] == hu["count"]
    assert hm["sum"] == pytest.approx(hu["sum"])
    for q in (0.5, 0.9, 0.99):
        est = quantile(hm, q)
        true = float(np.quantile(lat, q))
        # bucket upper bound: true <= est < 2*true (one octave resolution)
        assert true <= est <= 2.0 * true, (q, true, est)


def test_merge_counter_gauge_semantics():
    s1 = {"counters": {"serve.requests": 3},
          "gauges": {"tenants.hot": 2, "curvature.factor_age": 5,
                     "serve.queue_oldest_age_s": 0.2},
          "histograms": {}}
    s2 = {"counters": {"serve.requests": 4, "fleet.requests": 7},
          "gauges": {"tenants.hot": 1, "curvature.factor_age": 9,
                     "serve.queue_oldest_age_s": 0.1},
          "histograms": {}}
    m = merge([s1, s2, {}])
    assert m["counters"] == {"serve.requests": 7, "fleet.requests": 7}
    assert m["gauges"]["tenants.hot"] == 3          # occupancy sums
    assert m["gauges"]["curvature.factor_age"] == 9  # ages take max
    assert m["gauges"]["serve.queue_oldest_age_s"] == 0.2


def test_merge_rejects_mismatched_bounds():
    h1 = {"bounds": [1.0, 2.0], "counts": [1, 0, 0], "sum": 0.5, "count": 1}
    h2 = {"bounds": [1.0, 4.0], "counts": [0, 1, 0], "sum": 2.0, "count": 1}
    with pytest.raises(ValueError, match="bounds"):
        merge([{"histograms": {"h": h1}}, {"histograms": {"h": h2}}])


def test_quantile_edge_cases():
    # an empty histogram has no quantiles — nan, not a fake 0.0
    import math
    assert math.isnan(quantile({"bounds": default_buckets(),
                                "counts": [0] * 28, "sum": 0.0,
                                "count": 0}, 0.5))
    # all mass in overflow: the histogram only knows "above the top
    # bound" — inf, not the top finite bound understating the tail
    h = {"bounds": [1.0, 2.0], "counts": [0, 0, 5], "sum": 50.0, "count": 5}
    assert quantile(h, 0.5) == float("inf")
    assert quantile(h, 0.99) == float("inf")
    # mixed mass: finite quantiles stay finite, only the tail overflows
    h2 = {"bounds": [1.0, 2.0], "counts": [0, 3, 1], "sum": 9.0, "count": 4}
    assert quantile(h2, 0.5) == 2.0
    assert quantile(h2, 0.99) == float("inf")


def test_merge_min_gauges_and_condest():
    s1 = {"gauges": {"curvature.downdate_margin": 0.5,
                     "curvature.condest": 1e3, "health.verdict": 0.0}}
    s2 = {"gauges": {"curvature.downdate_margin": 0.01,
                     "curvature.condest": 1e6, "health.verdict": 1.0}}
    m = merge([s1, s2])
    assert m["gauges"]["curvature.downdate_margin"] == 0.01  # worst = min
    assert m["gauges"]["curvature.condest"] == 1e6           # worst = max
    assert m["gauges"]["health.verdict"] == 1.0              # worst = max


# ---------------------------------------------------------------------------
# ServerMetrics: bounded ring + queue-wait (satellite a)
# ---------------------------------------------------------------------------

def test_server_metrics_ring_bounded_but_totals_exact():
    m = ServerMetrics(window=8)
    for i in range(100):
        m.record(t_submit=float(i), t_done=float(i) + 0.01, tokens=2)
    s = m.summary()
    assert s["served"] == 100            # totals count everything
    assert len(m._ring) == 8             # percentiles over a bounded window
    assert s["p50_ms"] == pytest.approx(10.0, rel=0.2)


def test_server_metrics_reports_to_registry():
    reg = MetricsRegistry()
    m = ServerMetrics(window=8, registry=reg, prefix="serve")
    m.record(t_submit=0.0, t_done=0.5, tokens=3, queue_s=0.2)
    m.record(t_submit=1.0, t_done=1.1, tokens=1)     # no queue stamp
    snap = reg.snapshot()
    assert snap["counters"]["serve.requests"] == 2
    assert snap["counters"]["serve.tokens"] == 4
    assert snap["histograms"]["serve.request_latency_s"]["count"] == 2
    assert snap["histograms"]["serve.queue_wait_s"]["count"] == 1


def test_server_records_queue_wait_and_health_gauges():
    """An instrumented eager server emits the whole series family: request
    + queue-wait + solve histograms, queue gauges, curvature health."""
    reg = MetricsRegistry()
    tracer = Tracer()
    S = _window()
    srv = SolveServer(init_serve_state(S, 0.1),
                      batcher=TokenBudgetBatcher(max_requests=2),
                      adaptation=OnlineAdaptation(refresh_every=2,
                                                  drift_frac=None),
                      registry=reg, tracer=tracer)
    rng = np.random.default_rng(1)
    for i in range(4):
        rows = jnp.asarray(rng.normal(size=(1, 64)) / 8.0, jnp.float32)
        srv.submit(jnp.asarray(rng.normal(size=64), jnp.float32),
                   tokens=4, rows=rows)
    assert len(srv.flush()) == 4
    snap = reg.snapshot()
    assert snap["counters"]["serve.requests"] == 4
    assert snap["counters"]["serve.microbatches"] == 2
    assert snap["counters"]["curvature.folds"] == 4
    assert snap["counters"]["curvature.fold_rows"] == 4
    assert snap["histograms"]["serve.queue_wait_s"]["count"] == 4
    assert snap["histograms"]["serve.solve_latency_s"]["count"] == 2
    assert "curvature.factor_age" in snap["gauges"]
    assert "window.bytes.float32" in snap["gauges"]
    assert snap["gauges"]["window.bytes.float32"] == 8 * 64 * 4
    # refresh_every=2 -> the age policy fired at least once
    assert snap["counters"].get("curvature.refreshes", 0) >= 1
    names = {e["name"] for e in tracer.events()}
    assert {"request", "queue_wait", "serve.dispatch", "serve.wait",
            "adapt.fold", "adapt.refresh"} <= names
    refresh = [e for e in tracer.events() if e["name"] == "adapt.refresh"]
    assert all(e["args"]["reason"] == "age" and e["dur"] > 0
               for e in refresh)


def test_batcher_queue_stats():
    b = TokenBudgetBatcher(max_requests=4)
    assert b.queue_stats() == {"depth": 0, "pending_tokens": 0,
                               "oldest_age_s": 0.0}
    # t_submit is stamped by the server; emulate it on the request objects
    b.submit(np.zeros(4, np.float32), damping=0.1, tokens=3).t_submit = 10.0
    b.submit(np.zeros(4, np.float32), damping=0.1, tokens=5).t_submit = 11.0
    qs = b.queue_stats(now=12.0)
    assert qs["depth"] == 2 and qs["pending_tokens"] == 8
    assert qs["oldest_age_s"] == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# tracer + export
# ---------------------------------------------------------------------------

def test_tracer_span_ingest_drain_export(tmp_path):
    t = Tracer(pid=111)
    with t.span("request", trace="req1", args={"uid": 1}):
        pass
    t.add("rpc", cat="fleet", ts_us=1.0, dur_us=2.0, trace="req1")
    shipped = t.drain()
    assert len(shipped) == 2 and t.drain() == []     # drain clears pending
    other = Tracer(pid=222)
    other.ingest(shipped)
    other.add("request", ts_us=5.0, dur_us=1.0, trace="req1")
    evs = other.events()
    assert {e["pid"] for e in evs} == {111, 222}     # foreign pids kept
    assert all(e["args"]["trace"] == "req1" for e in evs)
    path = tmp_path / "trace.json"
    assert other.export(path) == 3
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    assert all(e["ph"] == "X" for e in doc["traceEvents"])


def test_tracer_bounded():
    t = Tracer(max_events=4)
    for i in range(10):
        t.add(f"e{i}", ts_us=float(i), dur_us=1.0)
    assert [e["name"] for e in t.events()] == ["e6", "e7", "e8", "e9"]


# ---------------------------------------------------------------------------
# exposition: Prometheus text, HTTP scrape, snapshot files
# ---------------------------------------------------------------------------

def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("serve.requests").inc(3)
    reg.gauge("tenants.hot").set(2)
    reg.histogram("serve.request_latency_s",
                  buckets=[0.001, 0.01]).observe(0.005)
    text = prometheus_text(reg.snapshot())
    assert "# TYPE serve_requests counter\nserve_requests 3" in text
    assert "tenants_hot 2" in text
    assert 'serve_request_latency_s_bucket{le="0.001"} 0' in text
    assert 'serve_request_latency_s_bucket{le="0.01"} 1' in text
    assert 'serve_request_latency_s_bucket{le="+Inf"} 1' in text
    assert "serve_request_latency_s_count 1" in text


def test_http_endpoint_scrape_and_fleet_merge():
    reg = MetricsRegistry()
    reg.counter("fleet.requests").inc(2)
    worker_snap = {"counters": {"serve.requests": 5}, "gauges": {},
                   "histograms": {}}
    srv, port = start_metrics_server(reg, port=0,
                                     extra_snapshots=lambda: [worker_snap])
    try:
        base = f"http://127.0.0.1:{port}"
        body = urllib.request.urlopen(f"{base}/metrics",
                                      timeout=10).read().decode()
        assert "fleet_requests 2" in body
        assert "serve_requests 5" in body            # merged-in worker view
        snap = json.loads(urllib.request.urlopen(
            f"{base}/metrics.json", timeout=10).read())
        assert snap["counters"] == {"fleet.requests": 2, "serve.requests": 5}
        assert urllib.request.urlopen(
            f"{base}/metrics", timeout=10).status == 200
    finally:
        srv.shutdown()


def test_write_snapshot_atomic(tmp_path):
    path = tmp_path / "nested" / "metrics.json"
    write_snapshot(str(path), {"counters": {"a": 1}, "gauges": {},
                               "histograms": {}})
    assert json.loads(path.read_text())["counters"] == {"a": 1}
    assert not path.with_suffix(".json.tmp").exists()


# ---------------------------------------------------------------------------
# tenants occupancy instrumentation
# ---------------------------------------------------------------------------

def test_tenant_manager_emits_occupancy_series():
    from repro.tenants import TenantManager

    reg = MetricsRegistry()
    mgr = TenantManager(2, registry=reg)
    state = init_serve_state(_window(), 0.1)
    rng = np.random.default_rng(2)
    for t in ("a", "b"):
        mgr.fold(state, t,
                 jnp.asarray(rng.normal(size=(1, 64)) / 8.0, jnp.float32))
        mgr.factor(state, t)
    mgr.evict("a")
    snap = reg.snapshot()
    assert snap["counters"]["tenants.evictions"] == 1
    assert snap["counters"]["tenants.materializations"] == 2
    assert snap["counters"]["tenants.folds"] == 2
    assert snap["counters"]["tenants.fold_rows"] == 2
    assert snap["gauges"]["tenants.registered"] == 2
    assert snap["gauges"]["tenants.spilled"] == 1
    assert snap["histograms"]["tenants.evict_latency_s"]["count"] == 1
    # touching the spilled tenant re-activates it (spill load + replay)
    mgr.delta(state, "a")
    snap = reg.snapshot()
    assert snap["counters"]["tenants.activations"] == 1
    assert snap["gauges"]["tenants.spilled"] == 0
    assert snap["histograms"]["tenants.activate_latency_s"]["count"] == 1


@pytest.mark.parametrize("phase", ["start_trace", "stop_trace"])
def test_profile_hooks_raise_when_the_trace_fails(monkeypatch, tmp_path,
                                                  phase):
    """With a trace directory asked for, a failed profiler call raises
    rather than leaving the run unprofiled in silence."""
    import jax

    def fail(*a, **k):
        raise RuntimeError(f"{phase} refused")

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(jax.profiler, phase, fail)
    hooks = ProfileHooks(str(tmp_path / "prof"))
    with pytest.raises(RuntimeError, match=f"{phase} refused"):
        hooks.start()
        hooks.stop()
    assert not hooks._active
    ProfileHooks(None).start()       # no directory: still a no-op
