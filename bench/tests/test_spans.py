"""The readers of the serving program's spans (``bench/spans.py`` and the
five ``bench/metrics/*.serve.py`` that use it): their arithmetic on
synthetic span lists, nothing from a capture without such spans, and
finite readings on a small serve capture recorded on a TPU v5e from the
tiny serve cell (``bench/tests/data/small-serve-v5e.xplane.pb``: a 0.4 s
window, its ``/host:metadata`` plane emptied to keep the file under
1 MB; the window's counters beside it in ``small-serve-v5e.json``)."""
import json
import shutil

import pytest

from tiny import BENCH, TESTS, load

spans = load(BENCH / "spans.py", "bench_spans")
trace = load(BENCH / "trace.py", "bench_trace")
WORKLOAD = "table1-n2048.serve-adapt"
READERS = ["queue_wait_p95_ms.serve", "release_hold_ms.serve",
           "idle_in_worker.serve", "submit_p99_ms.serve",
           "host_syncs_per_mb.serve"]
MS = 1_000_000                  # ns


def reader(name):
    return load(BENCH / "metrics" / f"{name}.py",
                "bench_metric_" + name.replace(".", "_"))


class Ctx:
    workload = WORKLOAD

    def __init__(self, parsed=None):
        if parsed is not None:          # what ``spans.load`` caches
            self._spans = {"spans": parsed}

    def module(self, name):
        return {"spans": spans, "trace": trace}[name]


class Tr:
    def __init__(self, window_s):
        self.window_s = window_s


class Cell:
    def __init__(self, counters):
        self.win_stats = {"counters": counters, "histograms": {}}


def S(name, start, end, line=0, **args):
    return spans.Span(name, start, end, args, line)


def test_queue_wait_joins_dispatch_uids_to_submits():
    sp = spans.Spans([S("serve.submit", 0, 1, 1, uid=1),
                      S("serve.submit", 10 * MS, 11 * MS, 1, uid=2),
                      S("serve.submit", 20 * MS, 21 * MS, 1, uid=3),
                      S("serve.dispatch", 50 * MS, 51 * MS, uids="1 2", k=2),
                      S("serve.dispatch", 80 * MS, 81 * MS, uids=3, k=1)],
                     [])
    # waits 50, 40 and 60 ms; numpy's linear 95th percentile of them
    got = reader("queue_wait_p95_ms.serve").read(Tr(1.0), None, Ctx(sp))
    assert got == pytest.approx(59.0)


def test_release_hold_runs_from_wait_end_to_its_release_end():
    sp = spans.Spans([S("serve.wait", 0, 10 * MS),
                      S("serve.release", 30 * MS, 32 * MS),
                      S("serve.release", 31 * MS, 33 * MS, line=1),
                      S("serve.wait", 40 * MS, 50 * MS),
                      S("serve.release", 60 * MS, 62 * MS)], [])
    got = reader("release_hold_ms.serve").read(Tr(1.0), None, Ctx(sp))
    assert got == pytest.approx((22 + 12) / 2)


def test_idle_in_worker_is_idle_inside_microbatches_only():
    busy = trace._union([(0, 5), (8, 20), (30, 40)])
    sp = spans.Spans([S("serve.microbatch", 2, 12),
                      S("serve.microbatch", 25, 35)], busy)
    # [2, 12): busy 3 + 4, idle 3; [25, 35): busy 5, idle 5
    got = reader("idle_in_worker.serve").read(Tr(100e-9), None, Ctx(sp))
    assert got == pytest.approx(8.0)
    assert sp.busy_in(0, 100) == 5 + 12 + 10


def test_submit_p99_of_submit_durations():
    sp = spans.Spans([S("serve.submit", 0, i * MS, uid=i)
                      for i in range(1, 101)], [])
    got = reader("submit_p99_ms.serve").read(Tr(1.0), None, Ctx(sp))
    assert got == pytest.approx(99.01)


def test_host_syncs_per_microbatch_from_counters():
    r = reader("host_syncs_per_mb.serve")
    assert r.read(None, Cell({"serve.host_syncs": 90,
                              "serve.microbatches": 12}), None) == 7.5


def test_nothing_read_from_a_program_without_spans_or_counter():
    empty = spans.Spans([], [])
    for name in READERS[:-1]:
        assert reader(name).read(Tr(1.0), None, Ctx(empty)) is None
    assert reader("host_syncs_per_mb.serve").read(
        None, Cell({"serve.microbatches": 12}), None) is None


def test_no_capture_reads_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    assert spans.load(Ctx()) is None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The recorded capture where ``bench/run.py`` leaves a cell's."""
    root = tmp_path_factory.mktemp("bench_trace")
    d = root / WORKLOAD / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(TESTS / "data" / "small-serve-v5e.xplane.pb", d)
    meta = json.load(open(TESTS / "data" / "small-serve-v5e.json"))
    return root, meta


def test_all_five_finite_on_a_recorded_v5e_serve_capture(recorded,
                                                          monkeypatch):
    root, meta = recorded
    monkeypatch.setattr(spans, "TRACE_DIR", root)
    ctx, tr, cell = Ctx(), Tr(meta["window_s"]), Cell(meta["counters"])
    sp = spans.load(ctx)
    assert sp.busy and sp.named("serve.microbatch")
    assert spans.load(ctx) is sp                    # parsed once per run
    got = {name: reader(name).read(tr, cell, ctx) for name in READERS}
    assert all(v is not None and v == v and v >= 0 for v in got.values())
    assert 0 < got["idle_in_worker.serve"] < 100
    # every dispatched uid was submitted inside the capture
    submitted = {int(s.args["uid"]) for s in sp.named("serve.submit")}
    assert {u for d in sp.named("serve.dispatch")
            for u in spans.uids(d)} <= submitted
