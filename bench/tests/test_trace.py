"""The trace reduction on a small trace recorded on a TPU v5e: five calls
of a jitted 1024×1024×1024 bf16 matmul with tanh, each inside a
``bench.small`` host span (bench/tests/data/small-v5e.xplane.pb)."""
import json

import pytest

from tiny import BENCH, TESTS, load

trace = load(BENCH / "trace.py", "bench_trace")
PEAKS = json.load(open(BENCH / "peaks.json"))["kinds"]["TPU v5 lite"]


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_xplane(str(TESTS / "data" / "small-v5e.xplane.pb"),
                               window_s=0.05, chips=1)


def test_programs_by_name(reduced):
    assert reduced.module_n == {"jit_small_matmul": 5}
    assert reduced.programs_n(r"^jit_small_matmul$") == 5
    assert reduced.programs_s(r"small_matmul") == pytest.approx(
        reduced.module_s["jit_small_matmul"])


def test_busy_is_union_of_ops_within_programs(reduced):
    assert 0 < reduced.busy_s <= reduced.module_s["jit_small_matmul"] * 1.01
    assert reduced.busy_s <= reduced.window_s


def test_matmul_rate_is_under_peak(reduced):
    per_call = reduced.ops_s(r"convolution_tanh_fusion") / 5
    share = 2 * 1024 ** 3 / per_call / PEAKS["bf16_flops_per_s"]
    assert 0.3 < share < 1.0


def test_host_spans_and_idle_gaps(reduced):
    assert reduced.host_spans["bench.small"] > reduced.busy_s
    gaps = [s for s, _ in reduced.gaps]
    assert gaps == sorted(gaps, reverse=True) and gaps[0] > 0
    assert any(label.startswith("bench.small") for _, label in reduced.gaps)
    bd = reduced.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_union_merges_overlaps():
    assert trace._union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert trace.module_base("jit_train_step(1246260598689600165)") == \
        "jit_train_step"


def test_self_time_subtracts_nested_ops():
    evs = [(0, 10, "while"), (1, 4, "a"), (5, 9, "b"), (12, 13, "a")]
    t = trace._self_times(evs)
    assert t == {"while": 3, "a": 4, "b": 4}
