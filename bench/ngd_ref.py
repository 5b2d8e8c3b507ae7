"""Plain reference of the damped natural-gradient update (arXiv 2310.17556).

    x   = (SᵀS + λI)⁻¹ v        via the n×n dual: x = (v − Sᵀ(SSᵀ+λI)⁻¹Sv)/λ,
                                or x = Sᵀ(SSᵀ+λI)⁻¹c where v = Sᵀc
    buf = μ·buf + x
    θ   = θ − lr_t·buf          lr_t warm-up then cosine

float32 at ``Precision.HIGHEST`` unless ``precision`` says otherwise; no
kernels, nothing of the program under test.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.scipy.linalg import cho_solve

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def damped_solve(S, V, lam, *, precision=HI):
    """x = (SᵀS + λI)⁻¹ V for S (n, m) and V (m,) or (m, k), through the
    Cholesky factor of the damped n×n Gram."""
    S = S.astype(F32)
    V = V.astype(F32)
    n = S.shape[0]
    W = jnp.matmul(S, S.T, precision=precision) + lam * jnp.eye(n, dtype=F32)
    c = jnp.linalg.cholesky(W)
    w = cho_solve((c, True), jnp.matmul(S, V, precision=precision))
    return (V - jnp.matmul(S.T, w, precision=precision)) / lam


def dual_solve(S, c, lam, *, precision=HI):
    """x = Sᵀ(SSᵀ + λI)⁻¹c = (SᵀS + λI)⁻¹Sᵀc for a right-hand side given
    by its coefficients c (n,) over the rows of S."""
    S = S.astype(F32)
    n = S.shape[0]
    W = jnp.matmul(S, S.T, precision=precision) + lam * jnp.eye(n, dtype=F32)
    c_ = jnp.linalg.cholesky(W)
    return jnp.matmul(S.T, cho_solve((c_, True), c.astype(F32)),
                      precision=precision)


def warmup_cosine(step: int, *, peak: float, warmup: int, total: int,
                  final_frac: float = 0.1) -> float:
    if step < warmup:
        return peak * step / max(warmup, 1)
    t = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return peak * (final_frac + (1 - final_frac) * 0.5
                   * (1 + math.cos(math.pi * t)))


def leaf_norms(tree):
    return jnp.stack([jnp.linalg.norm(a.astype(F32).reshape(-1))
                      for a in jax.tree.leaves(tree)])
