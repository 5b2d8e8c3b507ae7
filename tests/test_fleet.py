"""Fleet serving tier: wire protocol round-trips, fold-journal replay
(bit-identical factor reconstruction), gossip sequencing, dispatcher unit
tests against in-process fake workers (routing policies, failure
rerouting with request replay, draining shutdown), and the end-to-end
subprocess fleet — 2 real workers on localhost sockets, mixed-λ traces
with window folds, reconciled agreement per routing policy, fleet
checkpoint manifest + cross-process journal replay.
"""
import json
import os
import socket
import threading

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from repro.fleet import (  # noqa: E402
    Channel,
    Dispatcher,
    GossipLog,
    HashRing,
    ReplayBuffer,
    WorkerHandle,
    launch_fleet,
)
from repro.fleet import wire  # noqa: E402
from repro.fleet.wire import get_blocks, put_blocks  # noqa: E402
from repro.serve import (  # noqa: E402
    FoldJournal,
    OnlineAdaptation,
    SolveServer,
    TokenBudgetBatcher,
    init_serve_state,
)
from repro.serve.journal import FoldEvent  # noqa: E402


def _chan_pair():
    a, b = socket.socketpair()
    return Channel(a, name="a"), Channel(b, name="b")


def _window(n=8, m=64, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, m)) / np.sqrt(m), jnp.float32)


# ---------------------------------------------------------------------------
# wire protocol
# ---------------------------------------------------------------------------

def test_wire_roundtrip_dense_and_blocked():
    a, b = _chan_pair()
    try:
        v = np.arange(12, dtype=np.float32)
        blocks = (np.ones((2, 3), np.float32), np.zeros((2, 5), np.float64))
        arrays, meta = {}, {"uid": 7, "damping": None, "tag": "x"}
        put_blocks(arrays, meta, "v", v)
        put_blocks(arrays, meta, "rows", blocks)
        a.send("solve", meta, arrays)
        msg = b.recv(timeout=10)
        assert msg.kind == "solve"
        assert msg.meta["uid"] == 7 and msg.meta["damping"] is None
        np.testing.assert_array_equal(get_blocks(msg, "v"), v)
        got = get_blocks(msg, "rows")
        assert isinstance(got, tuple) and len(got) == 2
        np.testing.assert_array_equal(got[1], blocks[1])
        assert get_blocks(msg, "missing") is None
        # array-free frames skip the npz body entirely
        b.send("pong", {"queued": 0})
        assert a.recv(timeout=10).kind == "pong"
    finally:
        a.close()
        b.close()


def test_wire_json_fallback_interoperates(monkeypatch):
    """A sender without msgpack emits JSON headers; any receiver decodes
    them (per-frame codec byte)."""
    a, b = _chan_pair()
    try:
        monkeypatch.setattr(wire, "_msgpack", None)
        a.send("ping", {"barrier": True})
        msg = b.recv(timeout=10)
        assert msg.kind == "ping" and msg.meta["barrier"] is True
    finally:
        a.close()
        b.close()


def test_wire_peer_close_raises_wireerror():
    a, b = _chan_pair()
    a.close()
    with pytest.raises(wire.WireError):
        b.recv(timeout=10)
    b.close()


# ---------------------------------------------------------------------------
# fold journal: serialize -> replay == origin, bit for bit
# ---------------------------------------------------------------------------

def test_fold_journal_replay_bit_identical(tmp_path):
    S = _window()
    rng = np.random.default_rng(1)
    journal = FoldJournal()
    adapt = OnlineAdaptation(refresh_every=10 ** 6, drift_frac=None,
                             journal=journal)
    srv = SolveServer(init_serve_state(S, 0.1), adaptation=adapt)
    for _ in range(5):            # 5 folds of 3 rows wrap the n=8 FIFO
        srv.apply_fold(jnp.asarray(
            rng.normal(size=(3, 64)) / 8.0, jnp.float32))
    srv.refresh()                 # refresh events replay too
    srv.apply_fold(jnp.asarray(
        rng.normal(size=(2, 64)) / 8.0, jnp.float32))
    assert [e.kind for e in journal.events] == ["fold"] * 5 + \
        ["refresh", "fold"]

    path = tmp_path / "journal.npz"
    journal.save(path)
    loaded = FoldJournal.load(path)
    assert [e.slots for e in loaded.events] == \
        [e.slots for e in journal.events]

    replayed = loaded.replay(
        init_serve_state(S, 0.1),
        OnlineAdaptation(refresh_every=10 ** 6, drift_frac=None))
    for name in ("S", "W", "L", "slot"):
        np.testing.assert_array_equal(
            np.asarray(getattr(srv.state, name)),
            np.asarray(getattr(replayed, name)), err_msg=name)


def test_fold_out_of_order_replay_raises():
    S = _window()
    adapt = OnlineAdaptation(refresh_every=10 ** 6, drift_frac=None)
    state = init_serve_state(S, 0.1)
    rows = jnp.zeros((2, 64), jnp.float32)
    with pytest.raises(ValueError, match="out of order"):
        adapt.fold(state, rows, slots=(3, 4))
    state = adapt.fold(state, rows, slots=(0, 1))   # correct cursor ok
    assert int(state.slot) == 2


def test_gossip_log_and_replay_buffer():
    log = GossipLog(5)
    e0 = log.append(np.zeros((2, 4), np.float32))
    e1 = log.append(np.zeros((2, 4), np.float32))
    e2 = log.append(np.zeros((3, 4), np.float32))
    assert e0.slots == (0, 1) and e1.slots == (2, 3)
    assert e2.slots == (4, 0, 1)                     # FIFO wrap
    assert log.head == 3 and len(log.since(1)) == 2

    buf = ReplayBuffer()
    assert buf.offer(e2) == []                       # gap: buffered
    assert buf.offer(e1) == []
    assert [e.seq for e in buf.offer(e0)] == [0, 1, 2]
    assert buf.offer(e1) == []                       # duplicate dropped
    assert buf.applied == 3 and len(buf) == 0


def test_fold_journal_compaction_and_tail_replay(tmp_path):
    journal = FoldJournal()
    for i in range(6):
        journal.append_fold((i % 4,), np.full((1, 3), i, np.float32))
    assert journal.head == 6 and journal.total_k == 6
    assert journal.compact(4) == 4
    assert (journal.base, journal.base_k) == (4, 4)
    # absolute sequencing and the row count survive the truncation
    assert journal.head == 6 and journal.total_k == 6
    assert [e.seq for e in journal.events_since(4)] == [4, 5]
    with pytest.raises(ValueError, match="checkpoint"):
        journal.events_since(3)          # predates the compacted prefix

    p = tmp_path / "compacted.npz"
    journal.save(p)
    loaded = FoldJournal.load(p)
    assert (loaded.base, loaded.base_k, loaded.head) == (4, 4, 6)
    assert loaded.compact(2) == 0        # below base: no-op
    assert loaded.compact(100) == 2      # beyond head: clamps
    assert loaded.head == 6 and len(loaded.events) == 0


def test_gossip_log_compaction_keeps_cursor_continuity():
    log = GossipLog(5)
    for _ in range(4):
        log.append(np.zeros((2, 4), np.float32))   # 8 rows through n=5
    log.compact(3)
    assert log.base == 3 and len(log.since(3)) == 1
    # the FIFO cursor keeps counting the truncated prefix's rows
    assert log.append(np.zeros((1, 4), np.float32)).slots == (8 % 5,)
    with pytest.raises(ValueError):
        log.since(1)
    # a log resumed from the compacted journal lands on the same cursor
    resumed = GossipLog(5, journal=log.journal)
    assert resumed.slot == log.slot
    assert resumed.append(np.zeros((1, 4), np.float32)).slots == (9 % 5,)


def test_hash_ring_minimal_remap():
    ring = HashRing(str(i) for i in range(8))
    keys = [f"tenant{i}" for i in range(2000)]
    before = {k: ring.lookup(k) for k in keys}
    ring.remove("3")
    after = {k: ring.lookup(k) for k in keys}
    moved = [k for k in keys if before[k] != after[k]]
    # only the removed member's keys move, and that's ~1/8 of the space
    assert moved and all(before[k] == "3" for k in moved)
    assert len(moved) < len(keys) * 2.5 / 8
    ring.add("3")                        # rejoining restores placement
    assert {k: ring.lookup(k) for k in keys} == before
    # avoid= (a dead-but-listed member) spills only its keys
    spill = {k: ring.lookup(k, avoid={"3"}) for k in keys}
    assert all(v != "3" for v in spill.values())
    assert all(spill[k] == before[k] for k in keys if before[k] != "3")


# ---------------------------------------------------------------------------
# dispatcher unit tests with an in-process fake worker
# ---------------------------------------------------------------------------

class FakeWorker:
    """Protocol-speaking worker stub on a socketpair: answers solves with
    a worker-id-stamped echo, tracks folds, and can hold replies or die
    on command — the timing/failure control the real worker can't give a
    unit test."""

    def __init__(self, worker_id, *, n=8, hold=False):
        self.worker_id = worker_id
        self.n = n
        self.received = []          # uids in arrival order
        self.folds = []             # seqs in applied order
        self.hold = threading.Event()
        if not hold:
            self.hold.set()
        self._die = threading.Event()
        here, there = socket.socketpair()
        self.chan = Channel(here, name=f"fake{worker_id}")
        self.peer = Channel(there, name=f"disp{worker_id}")
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def die(self):
        self._die.set()
        self.hold.set()

    def _run(self):
        try:
            while True:
                msg = self.chan.recv()
                if msg.kind == "init":
                    self.chan.send("init_ok", {"worker_id": self.worker_id,
                                               "n": self.n})
                elif msg.kind == "solve":
                    self.hold.wait(30)
                    if self._die.is_set():
                        self.chan.close()       # swallow request, drop link
                        return
                    self.received.append(msg.meta["uid"])
                    arrays = {}
                    meta = {"uid": msg.meta["uid"],
                            "damping": msg.meta.get("damping") or 0.1,
                            "latency_s": 0.0}
                    put_blocks(arrays, meta, "x",
                               get_blocks(msg, "v") + self.worker_id)
                    self.chan.send("result", meta, arrays)
                elif msg.kind == "fold":
                    self.folds.append(msg.meta["seq"])
                elif msg.kind == "ping":
                    self.chan.send("pong", {"worker_id": self.worker_id,
                                            "queued": 0,
                                            "applied": len(self.folds),
                                            "served": len(self.received)})
                elif msg.kind == "drain":
                    self.chan.send("drained", {"worker_id": self.worker_id})
                elif msg.kind == "bye":
                    return
        except wire.WireError:
            return
        finally:
            self.chan.close()


def _fake_fleet(n_workers, route, *, gossip=True, hold=()):
    fakes = [FakeWorker(i, hold=i in hold) for i in range(n_workers)]
    disp = Dispatcher([WorkerHandle(f.worker_id, f.peer) for f in fakes],
                      route=route, gossip=gossip)
    disp.init_workers({"mode": "inline", "damping": 0.1})
    return disp, fakes


def test_dispatcher_round_robin_spreads_evenly():
    disp, fakes = _fake_fleet(2, "round_robin")
    try:
        for i in range(6):
            disp.submit(np.full(4, i, np.float32))
        results = disp.flush(timeout=30)
        assert len(results) == 6
        assert [r.uid for r in results] == list(range(6))   # FIFO order
        assert len(fakes[0].received) == 3
        assert len(fakes[1].received) == 3
    finally:
        disp.shutdown(timeout=10)


def test_dispatcher_by_adapter_sticky():
    disp, fakes = _fake_fleet(3, "by_adapter")
    try:
        for i in range(12):
            disp.submit(np.zeros(4, np.float32), adapter=f"user{i % 4}")
        disp.flush(timeout=30)
        # every request of one adapter landed on one worker
        for a in range(4):
            uids = [u for u in range(12) if u % 4 == a]
            assert len({disp.assignments[u] for u in uids}) == 1
        # and the adapters actually spread over >1 worker
        assert len({disp.assignments[u] for u in range(12)}) > 1
    finally:
        disp.shutdown(timeout=10)


def test_dispatcher_by_adapter_placement_survives_failure():
    """Consistent-hash property end to end: losing one worker moves only
    the adapters that lived on it — every other adapter keeps its worker
    (and therefore its accreted tenant/window state)."""
    disp, fakes = _fake_fleet(3, "by_adapter")
    try:
        adapters = [f"user{i}" for i in range(9)]
        uid1 = {a: disp.submit(np.zeros(4, np.float32), adapter=a)
                for a in adapters}
        disp.flush(timeout=30)
        before = {a: disp.assignments[uid1[a]] for a in adapters}
        assert len(set(before.values())) == 3        # all workers used
        victim = before[adapters[0]]
        fakes[victim].die()
        uid2 = {a: disp.submit(np.zeros(4, np.float32), adapter=a)
                for a in adapters}
        results = disp.flush(timeout=30)
        assert len(results) == len(adapters)         # all still answered
        after = {a: disp.assignments[uid2[a]] for a in adapters}
        for a in adapters:
            if before[a] == victim:
                assert after[a] != victim            # spilled off the dead
            else:
                assert after[a] == before[a], a      # placement preserved
    finally:
        disp.shutdown(drain=False, timeout=10)


def test_dispatcher_least_loaded_avoids_busy_worker():
    disp, fakes = _fake_fleet(2, "least_loaded", hold={0, 1})
    try:
        first = disp.submit(np.zeros(4, np.float32))
        busy = disp.assignments[first]
        other = 1 - busy
        fakes[other].hold.set()          # the other worker serves freely
        for _ in range(5):
            disp.submit(np.zeros(4, np.float32))
            # wait until only the held request is in flight, so the next
            # routing decision sees the true (1 vs 0) load split
            deadline = disp.clock() + 10
            while disp.pending() > 1 and disp.clock() < deadline:
                disp._pump(0.01)
        fakes[busy].hold.set()
        disp.flush(timeout=30)
        later = [disp.assignments[u] for u in range(1, 6)]
        assert all(w == other for w in later), later
    finally:
        disp.shutdown(timeout=10)


def test_dispatcher_failure_reroutes_inflight():
    disp, fakes = _fake_fleet(2, "round_robin", hold={0, 1})
    try:
        uids = [disp.submit(np.full(4, i, np.float32)) for i in range(6)]
        victim = disp.assignments[uids[0]]
        survivor = 1 - victim
        fakes[victim].die()              # close mid-flight, swallow one
        fakes[survivor].hold.set()
        results = disp.flush(timeout=30)
        assert len(results) == 6         # every request still answered
        assert all(disp.assignments[u] == survivor for u in uids)
        assert not disp.workers[victim].alive
        # all results computed by the survivor (x = v + worker_id)
        for r in results:
            assert float(r.x[0]) == r.uid + survivor
    finally:
        disp.shutdown(timeout=10)


def test_dispatcher_all_workers_dead_raises():
    disp, fakes = _fake_fleet(1, "round_robin", hold={0})
    disp.submit(np.zeros(4, np.float32))
    fakes[0].die()
    with pytest.raises(RuntimeError, match="no alive workers"):
        disp.flush(timeout=30)
    disp.shutdown(drain=False, timeout=10)


def test_dispatcher_drain_shutdown_serves_queue():
    disp, fakes = _fake_fleet(2, "round_robin")
    try:
        uids = [disp.submit(np.zeros(4, np.float32)) for _ in range(4)]
        disp.shutdown(drain=True, timeout=30)
        assert disp.metrics.summary()["served"] == 4
        assert all(not w.alive for w in disp.workers)
    finally:
        for f in fakes:
            f.peer.close()


def test_dispatcher_gossip_broadcasts_to_all():
    disp, fakes = _fake_fleet(2, "round_robin", gossip=True)
    try:
        rows = np.zeros((2, 4), np.float32)
        disp.submit(np.zeros(4, np.float32), rows=rows)
        disp.submit(np.zeros(4, np.float32), rows=rows)
        disp.flush(timeout=30)
        disp.reconcile(timeout=30)
        assert fakes[0].folds == [0, 1]
        assert fakes[1].folds == [0, 1]
        assert disp.log.head == 2
        assert disp.log.events[0].slots == (0, 1)
        assert disp.log.events[1].slots == (2, 3)
    finally:
        disp.shutdown(timeout=10)


# ---------------------------------------------------------------------------
# end-to-end: real subprocess workers over localhost sockets
# ---------------------------------------------------------------------------

def _mixed_trace(m, requests, seed=2):
    rng = np.random.default_rng(seed)
    trace = []
    for i in range(requests):
        trace.append((
            rng.normal(size=(m,)).astype(np.float32),
            0.3 if i % 5 == 4 else None,
            (rng.normal(size=(2, m)) / np.sqrt(m)).astype(np.float32)
            if i % 3 == 2 else None,
            f"user{i % 5}"))     # user0-3 and user4 hash to different
    return trace                 # workers of a 2-fleet (crc32 % 2)


def _eager_fold_at_admission(S, trace, damping, k):
    srv = SolveServer(init_serve_state(S, damping),
                      batcher=TokenBudgetBatcher(max_requests=k),
                      adaptation=OnlineAdaptation(refresh_every=10 ** 6,
                                                  drift_frac=None))
    out, sub = {}, {}
    for i, (v, lam, rows, _) in enumerate(trace):
        if rows is not None:
            for r in srv.flush():
                out[sub[r.uid]] = np.asarray(r.x)
            srv.apply_fold(rows)
        sub[srv.submit(v, damping=lam)] = i
    for r in srv.flush():
        out[sub[r.uid]] = np.asarray(r.x)
    return out, srv


@pytest.mark.slow
def test_subprocess_fleet_mixed_trace_reconciles(tmp_path):
    """The CI fleet smoke: dispatcher + 2 real worker subprocesses on
    localhost, short mixed-λ trace with folds. Per-request agreement vs
    the fold-at-admission eager reference ≤5e-3, post-reconcile probes
    bit-identical, fleet checkpoint manifest written, and the gossiped
    journal replayed on a fresh ServeState reproduces each worker's
    checkpointed factor bit for bit."""
    n, m, requests, k = 8, 96, 12, 2
    S = _window(n, m, seed=3)
    trace = _mixed_trace(m, requests)
    ref, _ = _eager_fold_at_admission(S, trace, 0.1, k)

    disp = launch_fleet(2, init_meta={"mode": "inline", "damping": 0.1,
                                      "max_requests": k,
                                      "refresh_every": 10 ** 6,
                                      "drift_frac": None},
                        init_arrays={"S0": np.asarray(S)},
                        route="round_robin", gossip=True)
    try:
        sub = {}
        for i, (v, lam, rows, adapter) in enumerate(trace):
            sub[disp.submit(v, damping=lam, rows=rows,
                            adapter=adapter)] = i
        got = {sub[r.uid]: np.asarray(r.x) for r in disp.flush(timeout=300)}
        assert sorted(got) == sorted(ref)
        worst = max(np.linalg.norm(got[i] - ref[i])
                    / np.linalg.norm(ref[i]) for i in ref)
        assert worst < 5e-3, worst

        disp.reconcile(timeout=300)
        probe = disp.probe(np.asarray(trace[0][0]), timeout=300)
        xs = [np.asarray(x) for x in probe.values()]
        assert len(xs) == 2
        np.testing.assert_array_equal(xs[0], xs[1])

        manifest_path = disp.checkpoint(tmp_path, 7, timeout=300)
        manifest = json.loads(manifest_path.read_text())
        assert manifest["route"] == "round_robin"
        assert manifest["gossip_head"] == disp.log.head > 0
        assert set(manifest["workers"]) == {"0", "1"}

        # cross-process replay: gossip journal + fresh state == each
        # worker's checkpointed window, bit for bit
        from repro.serve import restore_serve_state
        gossip = FoldJournal.load(tmp_path / manifest["gossip_journal"])
        replayed = gossip.replay(
            init_serve_state(S, 0.1),
            OnlineAdaptation(refresh_every=10 ** 6, drift_frac=None))
        for wid in (0, 1):
            wdir = tmp_path / f"worker_{wid}"
            wstate, meta = restore_serve_state(
                wdir, 7, init_serve_state(S, 0.1))
            assert meta["worker_id"] == wid
            for name in ("S", "W", "L", "slot"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(replayed, name)),
                    np.asarray(getattr(wstate, name)),
                    err_msg=f"worker {wid} {name}")
    finally:
        disp.shutdown(timeout=60)


def test_launch_fleet_refuses_at_once_on_a_tpu_parent(monkeypatch):
    """A process that holds the TPU must not spawn fleet workers (they
    could not open the chip): launch_fleet raises before it listens or
    spawns anything, instead of waiting out spawn_timeout."""
    import subprocess
    import time

    import jax

    from repro.fleet import dispatcher

    jax.devices()                          # this process holds its backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def no_spawn(*a, **k):
        raise AssertionError("spawned a worker")

    monkeypatch.setattr(subprocess, "Popen", no_spawn)
    monkeypatch.setattr(dispatcher, "listen", no_spawn)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="holds the TPU"):
        launch_fleet(2, init_meta={"mode": "inline"}, spawn_timeout=300.0)
    assert time.monotonic() - t0 < 5.0


@pytest.mark.slow
def test_subprocess_fleet_by_adapter_partitions_exactly():
    """Gossip off + by_adapter: each worker's responses are bit-identical
    to an eager server driven with that worker's sub-trace — folds
    partition cleanly. Width-1 microbatches pin the batch composition
    (socket timing otherwise decides coalescing, which moves fp
    rounding), so the bit-exactness is deterministic."""
    n, m, requests, k = 8, 96, 12, 1
    S = _window(n, m, seed=4)
    trace = _mixed_trace(m, requests, seed=5)
    disp = launch_fleet(2, init_meta={"mode": "inline", "damping": 0.1,
                                      "max_requests": k,
                                      "refresh_every": 10 ** 6,
                                      "drift_frac": None},
                        init_arrays={"S0": np.asarray(S)},
                        route="by_adapter", gossip=False)
    try:
        sub = {}
        for i, (v, lam, rows, adapter) in enumerate(trace):
            sub[disp.submit(v, damping=lam, rows=rows,
                            adapter=adapter)] = i
        got = {sub[r.uid]: np.asarray(r.x) for r in disp.flush(timeout=300)}
        by_worker = {}
        for uid, i in sub.items():
            by_worker.setdefault(disp.assignments[uid], []).append(i)
        assert len(by_worker) == 2           # adapters actually spread
        for wid, idxs in by_worker.items():
            srv = SolveServer(init_serve_state(S, 0.1),
                              batcher=TokenBudgetBatcher(max_requests=k),
                              adaptation=OnlineAdaptation(
                                  refresh_every=10 ** 6, drift_frac=None))
            ssub = {}
            for i in sorted(idxs):
                v, lam, rows, _ = trace[i]
                ssub[srv.submit(v, damping=lam, rows=rows)] = i
            sref = {ssub[r.uid]: np.asarray(r.x) for r in srv.flush()}
            for i in sorted(idxs):
                np.testing.assert_array_equal(got[i], sref[i],
                                              err_msg=f"w{wid} req{i}")
    finally:
        disp.shutdown(timeout=60)


@pytest.mark.slow
def test_subprocess_fleet_obs_merges_metrics_and_stitches_traces(tmp_path):
    """Fleet observability end to end: worker registries ship snapshots in
    heartbeat pongs and ``fleet_metrics`` merges them with the
    dispatcher's own front-tier series; trace ids ride the solve frames
    out and the workers' spans ride the result frames home, so one trace
    id collects spans from >=2 distinct processes — the stitching the
    Chrome-trace export relies on."""
    n, m, requests, k = 8, 96, 8, 2
    S = _window(n, m, seed=6)
    trace = _mixed_trace(m, requests, seed=7)

    from repro.obs import MetricsRegistry, quantile
    registry = MetricsRegistry()
    disp = launch_fleet(2, init_meta={"mode": "inline", "damping": 0.1,
                                      "max_requests": k,
                                      "refresh_every": 10 ** 6,
                                      "drift_frac": None,
                                      "obs": True, "trace": True,
                                      "audit_every": 2},
                        init_arrays={"S0": np.asarray(S)},
                        route="round_robin", gossip=True,
                        registry=registry)
    try:
        for i, (v, lam, rows, adapter) in enumerate(trace):
            disp.submit(v, damping=lam, rows=rows, adapter=adapter)
        assert len(disp.flush(timeout=300)) == requests

        # heartbeat surfaces batcher queue state (satellite b)
        reports = disp.heartbeat(timeout=300)
        for rep in reports.values():
            assert rep["queue_depth"] == 0       # drained by flush
            assert rep["oldest_age_s"] == 0.0

        # merged fleet view: worker serve.* sums, dispatcher fleet.* rides
        # along under its own prefix (no double counting)
        snap = disp.fleet_metrics(refresh=False)  # heartbeat above refreshed
        assert snap["counters"]["serve.requests"] == requests
        assert snap["counters"]["fleet.requests"] == requests
        per_worker = [w.metrics for w in disp.workers if w.metrics]
        assert len(per_worker) == 2
        counts = [p["counters"].get("serve.requests", 0) for p in per_worker]
        assert sum(counts) == requests and all(c > 0 for c in counts)
        h = snap["histograms"]["serve.request_latency_s"]
        assert h["count"] == requests
        assert 0.0 < quantile(h, 0.5) <= quantile(h, 0.99)
        assert snap["histograms"]["serve.queue_wait_s"]["count"] == requests

        # numerical-health rollup: a healthy fleet's merged verdict is
        # ok, the per-worker reports rode the same pongs, and the
        # cadenced audit published condest/margin gauges that min/max
        # merge into the fleet view
        fh = disp.fleet_health(refresh=False)
        assert fh["verdict"] == "ok" and fh["members"] == 2
        assert all(w.health.get("verdict") == "ok"
                   for w in disp.workers if w.alive)
        assert snap["gauges"]["curvature.downdate_margin"] > 1e-3
        assert np.isfinite(snap["gauges"]["curvature.condest"])
        assert snap["gauges"]["health.verdict"] == 0.0

        # cross-process stitching: worker spans (foreign pid) + the
        # dispatcher's rpc span share one trace id
        events = disp.tracer.events()
        by_trace = {}
        for e in events:
            tid = e.get("args", {}).get("trace")
            if tid is not None:
                by_trace.setdefault(tid, []).append(e)
        stitched = {tid: evs for tid, evs in by_trace.items()
                    if len({e["pid"] for e in evs}) >= 2}
        assert stitched, "no trace id spans >=2 processes"
        names = {e["name"] for evs in stitched.values() for e in evs}
        assert "request" in names and "rpc" in names

        out = tmp_path / "fleet_trace.json"
        assert disp.tracer.export(out) == len(events) > 0
        doc = json.loads(out.read_text())
        assert all(e["ph"] == "X" for e in doc["traceEvents"])
    finally:
        disp.shutdown(timeout=60)


def test_build_fleet_wiring():
    """build_fleet returns a dispatcher + traffic-side handles wired to
    the same window; the full request → solve → update loop runs."""
    from repro import configs
    from repro.launch.mesh import make_mesh
    from repro.launch.trainer import build_fleet

    cfg = configs.get_smoke("llama3.2-3b")
    mesh = make_mesh((1, 1), ("data", "model"))
    disp, h = build_fleet(cfg, mesh=mesh, n_workers=2, window=4, seq=8,
                          damping=1e-2, max_requests=2,
                          refresh_every=10 ** 6, drift_frac=None)
    try:
        ex = {kk: v[:2] for kk, v in h.data.batch_at(1).items()}
        loss, v, rows = h.score_grads(h.params, ex)
        uid = disp.submit(np.asarray(v), tokens=16, rows=np.asarray(rows),
                          adapter="userA")
        (res,) = disp.flush(timeout=300)
        assert res.uid == uid
        assert np.isfinite(np.linalg.norm(res.x))
        h.apply_update(res.x, lr=0.05)
        disp.reconcile(timeout=300)
        reports = disp.heartbeat()
        assert all(rep["applied"] == 1 for rep in reports.values())
    finally:
        disp.shutdown(timeout=60)
