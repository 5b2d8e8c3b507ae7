"""Pallas TPU kernel: tall-skinny Gram matrix  W = S · Sᵀ.

This is the paper's dominant cost term — O(n²·m) out of the total
O(n²·m + n³) — and the op the A100 implementation hands to cuBLAS. On TPU
we tile it for the MXU explicitly:

* grid = (n/bn, n/bn, m/bk); the K-reduction (parameter axis, the ~10⁶-long
  one) is the innermost, *sequential* grid dimension, so the (bn, bn) fp32
  accumulator tile is revisited in VMEM across the whole reduction and HBM
  sees exactly one read of S per output row-band and one write of W.
* both operands are row-bands of the *same* matrix S (blocks (i,k) and
  (j,k)) — the contraction is `dot_general` over the lane axis with
  ``preferred_element_type=float32``, the MXU's native bf16×bf16→fp32 mode.
* block sizes default to (bn=128 sublane-aligned, bk=512 lane-aligned);
  callers may tune. Inputs are padded in ``ops.py`` so every block is full.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mosaic import dot_f32

__all__ = ["gram_pallas", "gram_acc_pallas"]


def _gram_kernel(s_i_ref, s_j_ref, w_ref):
    """One (bn, bn) output tile; accumulates over the k (parameter) axis."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        w_ref[...] = jnp.zeros_like(w_ref)

    a = s_i_ref[...]
    b = s_j_ref[...]
    w_ref[...] += dot_f32(a, b, ((1,), (1,)))


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def gram_pallas(S: jax.Array, *, bn: int = 128, bk: int = 512,
                interpret: bool = False) -> jax.Array:
    """W = S @ S.T with fp32 accumulation. S must be padded to (bn, bk) tiles.

    Returns (n, n) float32.
    """
    n, m = S.shape
    assert n % bn == 0 and m % bk == 0, (n, m, bn, bk)
    grid = (n // bn, n // bn, m // bk)

    return pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bn, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="gram_ssT",
    )(S, S)


def _gram_acc_kernel(w_in_ref, s_i_ref, s_j_ref, w_ref):
    """Like ``_gram_kernel`` but seeded from an incoming accumulator tile
    instead of zeros — the chaining primitive for blocked (per-layer) S."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        w_ref[...] = w_in_ref[...]

    a = s_i_ref[...]
    b = s_j_ref[...]
    w_ref[...] += dot_f32(a, b, ((1,), (1,)))


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def gram_acc_pallas(S: jax.Array, W_in: jax.Array, *, bn: int = 128,
                    bk: int = 512, interpret: bool = False) -> jax.Array:
    """W = W_in + S @ S.T with fp32 accumulation; ``W_in`` is donated
    (aliased to the output), so chaining over B blocks keeps exactly one
    (n, n) accumulator live in HBM regardless of B.

    S must be padded to (bn, bk) tiles; W_in is (n, n) float32.
    """
    n, m = S.shape
    assert n % bn == 0 and m % bk == 0, (n, m, bn, bk)
    assert W_in.shape == (n, n) and W_in.dtype == jnp.float32, W_in
    grid = (n // bn, n // bn, m // bk)

    return pl.pallas_call(
        _gram_acc_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bn, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="gram_ssT_acc",
    )(W_in, S, S)
