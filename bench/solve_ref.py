"""Plain reference of the damped solve x = (SᵀS + λI)⁻¹V (Algorithm 1's
answer), through the Cholesky factor of the n×n Gram, in float32 with
``jax.scipy`` only and nothing of the program under test.

``precision`` names how the matmuls are computed: ``highest`` (float32,
``Precision.HIGHEST``) is the reference. The controls are the steps below
it, written out so that they mean the same on every backend: ``high``
splits each float32 operand into a bfloat16 head and tail and sums the
three products head·head, head·tail and tail·head (what a TPU does at
``Precision.HIGH``); ``default`` rounds each operand to bfloat16 once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.linalg import cho_solve

F32 = jnp.float32


def _bf16(a):
    """a rounded to bfloat16, kept in float32 (an explicit rounding that
    no compiler may drop as excess precision)."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def matmul(a, b, precision="highest"):
    a, b = a.astype(F32), b.astype(F32)
    hi = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.matmul(a, b, precision=hi)
    # products of bfloat16 values are exact in float32, so the passes
    # below run at HIGHEST and only the operand rounding is lost
    ah, bh = _bf16(a), _bf16(b)
    if precision == "default":
        return jnp.matmul(ah, bh, precision=hi)
    if precision == "high":
        al, bl = _bf16(a - ah), _bf16(b - bh)
        return (jnp.matmul(ah, bh, precision=hi)
                + jnp.matmul(ah, bl, precision=hi)
                + jnp.matmul(al, bh, precision=hi))
    raise ValueError(f"unknown precision {precision!r}")


def factor(S, lam, precision="highest"):
    """Lower Cholesky factor of SSᵀ + λI."""
    S = S.astype(F32)
    W = matmul(S, S.T, precision)
    return jnp.linalg.cholesky(W + lam * jnp.eye(S.shape[0], dtype=F32))


def solve(S, L, V, lam, precision="highest"):
    """x = (SᵀS + λI)⁻¹V for V (m,) or (m, k), given L from ``factor``."""
    S = S.astype(F32)
    w = cho_solve((L, True), matmul(S, V, precision))
    return (V - matmul(S.T, w, precision)) / lam
