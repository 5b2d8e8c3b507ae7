"""Pallas TPU kernel: rank-k Cholesky update/downdate  L·Lᵀ ± X·Xᵀ.

The streaming-curvature hot op (``repro/curvature/update.py`` is the
pure-JAX reference). Same single-invocation in-VMEM regime as the blocked
``cholesky`` kernel — n is the sample count (10²–10³), so L (n, n) and
X (n, k) both fit VMEM and the whole rank-k sweep runs without touching
HBM in between:

  outer ``fori_loop`` over the k update columns; inner ``fori_loop`` over
  the n factor columns, each step one plane rotation (circular for the
  update, hyperbolic for the downdate) expressed as two length-n VPU
  vector ops:

      r = √(a² ± b²);  L[:, j] ← (a·L[:, j] ± b·x)/r;  x ← (a·x − b·L[:, j])/r

  No masking is needed: above the diagonal both operands are already zero,
  and x[j] cancels exactly (−b·a + a·b). O(n²·k) VPU FLOPs — negligible
  next to the O(n²·m) Gram it replaces, which is the whole point.

The kernel works on the transposes U = Lᵀ and Xᵀ, so column j of L is row
j of a VMEM ref (a dynamic sublane slice Mosaic lowers), and the pivots
a = L[j, j], b = x[j] are picked with an iota mask and a lane reduction.
Larger n falls back to the jnp reference in ``ops.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.cholesky import MAX_SINGLE_BLOCK_N
from repro.kernels.mosaic import pick_lane

__all__ = ["cholupdate_pallas", "MAX_SINGLE_BLOCK_N"]


def _cholupdate_kernel(u_ref, xt_ref, out_ref, *, sign: int, eps: float):
    n = u_ref.shape[0]
    k = xt_ref.shape[0]
    out_ref[...] = u_ref[...].astype(jnp.float32)

    def col_sweep(t, carry):
        x = xt_ref[pl.ds(t, 1), :].astype(jnp.float32)          # (1, n)

        def rot(j, x):
            row = out_ref[pl.ds(j, 1), :]                       # L[:, j]ᵀ
            a = pick_lane(row, j)
            b = pick_lane(x, j)
            r = jnp.sqrt(jnp.maximum(a * a + sign * b * b, eps))
            out_ref[pl.ds(j, 1), :] = (a * row + sign * b * x) / r
            return (a * x - b * row) / r

        jax.lax.fori_loop(0, n, rot, x)
        return carry

    jax.lax.fori_loop(0, k, col_sweep, 0)
    # FMA contraction makes the a·b − b·a cancellations inexact at the
    # 1-ulp level; pin the strict lower triangle of U back to exactly zero.
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    out_ref[...] = jnp.where(rows <= cols, out_ref[...], 0.0)


@functools.partial(jax.jit, static_argnames=("sign", "eps", "interpret"))
def cholupdate_pallas(L: jax.Array, X: jax.Array, *, sign: int = 1,
                      eps: float = 1e-30,
                      interpret: bool = False) -> jax.Array:
    """L' with L'·L'ᵀ = L·Lᵀ + sign·X·Xᵀ. Real fp32, L (n, n) lower,
    X (n, k); sign ∈ {+1, −1}. Zero columns of X are exact no-ops, so
    callers may pad k freely."""
    n = L.shape[0]
    assert L.shape == (n, n) and X.shape[0] == n, (L.shape, X.shape)
    assert sign in (1, -1), sign
    U = pl.pallas_call(
        functools.partial(_cholupdate_kernel, sign=sign, eps=eps),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret,
        name="rank_k_cholupdate",
    )(L.T, X.T)
    return U.T
