"""Main-path Pallas kernels compiled for a described TPU v5e at real widths.

Nothing runs: each test lowers the ``repro.kernels.ops`` entry point with
``mode="kernel"`` for one chip of a ``v5e:2x2`` topology and compiles it
with the TPU compiler, which refuses what Mosaic cannot lower (dynamic lane
slices, unaligned tiles) and kernels that need more VMEM than they may use
or more HBM than the chip has.
Each compiled program must hold a ``tpu_custom_call`` — the kernel itself,
not a fallback to the reference.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# serve window at the benchmark's real shape: n samples, m parameters
# (a multiple of the 512 tile), k coalesced requests
N, M, K = 512, 25_088, 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # libtpu loads on the first topology call and would otherwise write its
    # compiler logs to a fixed directory outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("n", [N, ops.MAX_SINGLE_BLOCK_N])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_serve_solve_compiles(one_chip, dtype, n):
    fn = functools.partial(ops.serve_solve, mode="kernel")
    _compile(lambda S, L, V: fn(S, L, V, 0.2), one_chip,
             ((n, M), dtype), ((n, n), jnp.float32), ((M, K), jnp.float32))


# n=8 samples over whisper-base's 70,909,952 parameters: the kernels hold V
# and X as lane-dense (k, m) arrays; an (m, k) tile layout would need a
# lane-padded copy larger than the chip's HBM
WN, WM = 8, 70_909_952


@pytest.mark.parametrize("kernel", ["serve_solve", "sv_cross", "serve_apply"])
def test_serve_kernels_compile_at_whisper_base_width(one_chip, kernel):
    S, L, V, w = (((WN, WM), jnp.float32), ((WN, WN), jnp.float32),
                  ((WM, K), jnp.float32), ((WN, K), jnp.float32))
    fn = functools.partial(getattr(ops, kernel), mode="kernel")
    if kernel == "serve_solve":
        _compile(lambda S, L, V: fn(S, L, V, 0.2), one_chip, S, L, V)
    elif kernel == "sv_cross":
        _compile(fn, one_chip, S, V)
    else:
        _compile(lambda S, w, V: fn(S, w, V, 0.2), one_chip, S, w, V)


def test_sv_cross_compiles(one_chip):
    _compile(functools.partial(ops.sv_cross, mode="kernel"), one_chip,
             ((N, M), jnp.float32), ((M, K), jnp.float32))


def test_serve_apply_compiles(one_chip):
    fn = functools.partial(ops.serve_apply, mode="kernel")
    _compile(lambda S, w, V: fn(S, w, V, 0.2), one_chip,
             ((N, M), jnp.float32), ((N, K), jnp.float32),
             ((M, K), jnp.float32))


def test_fold_cols_compiles(one_chip):
    _compile(functools.partial(ops.fold_cols, mode="kernel"), one_chip,
             ((N, M), jnp.float32), ((K, M), jnp.float32))


@pytest.mark.parametrize("n", [N, ops.MAX_SINGLE_BLOCK_N])
@pytest.mark.parametrize("sign", [1, -1], ids=["update", "downdate"])
def test_cholupdate_compiles(one_chip, sign, n):
    fn = functools.partial(ops.cholupdate, sign=sign, mode="kernel")
    _compile(fn, one_chip, ((n, n), jnp.float32), ((n, K), jnp.float32))


@pytest.mark.parametrize("n", [N, ops.MAX_SINGLE_BLOCK_N])
def test_cholesky_compiles(one_chip, n):
    _compile(functools.partial(ops.cholesky, mode="kernel"), one_chip,
             ((n, n), jnp.float32))
