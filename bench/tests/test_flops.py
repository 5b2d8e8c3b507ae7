"""The yardstick's FLOP counts against XLA's own count of the plain
references, at tiny shapes on the CPU."""
import json

import jax
import jax.numpy as jnp
import pytest

from tiny import BENCH, TESTS, load

flops = load(BENCH / "flops.py", "bench_flops")
solve_ref = load(BENCH / "solve_ref.py", "bench_solve_ref")
whisper = load(BENCH / "configs" / "whisper-base.py", "bench_whisper_ref")


def xla_flops(fn, *args):
    return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


@pytest.mark.parametrize("n,m", [(64, 2000), (128, 4096)])
def test_chol_solve_flops_match_xla(n, m):
    S = jax.ShapeDtypeStruct((n, m), jnp.float32)
    v = jax.ShapeDtypeStruct((m,), jnp.float32)
    got = xla_flops(lambda S, v: solve_ref.solve(
        S, solve_ref.factor(S, 1e-3), v, 1e-3), S, v)
    assert flops.chol_solve_flops(n, m) == pytest.approx(got, rel=0.02)


def test_encdec_forward_flops_match_xla():
    c = json.load(open(TESTS / "whisper-smoke.json"))
    seq = 8
    shapes = whisper.param_shapes(c)
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
                          shapes, is_leaf=lambda s: isinstance(s, tuple))
    ex = {"frames": jax.ShapeDtypeStruct(
              (c["max_source_positions"], c["d_model"]), jnp.float32),
          "inputs": jax.ShapeDtypeStruct((seq,), jnp.int32),
          "labels": jax.ShapeDtypeStruct((seq,), jnp.int32),
          "mask": jax.ShapeDtypeStruct((seq,), jnp.float32)}
    got = xla_flops(lambda p, e: whisper.sample_logp(p, e, c), params, ex)
    # XLA also counts the elementwise work and the masked half of the
    # causal scores, which the yardstick leaves out
    ours = flops.encdec_forward_flops(c, seq=seq)
    assert 0.9 * got <= ours <= got


def test_window_pass_bytes_split_over_chips():
    one = flops.window_pass_bytes(64, 4096, 8, chips=1)
    four = flops.window_pass_bytes(64, 4096, 8, chips=4)
    assert one == 64 * 4096 * 4 + 2 * 4096 * 8 * 4
    assert four == pytest.approx(one / 4)
