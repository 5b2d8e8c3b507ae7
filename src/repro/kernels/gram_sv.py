"""Pallas TPU kernel: fused  (W, u) = (S·Sᵀ, S·v)  in ONE pass over S.

Beyond-paper optimization. Algorithm 1 reads S three times from HBM:
once for the Gram, once for u = S·v, once for the apply Sᵀw. The Gram and
the matvec share the identical S traffic pattern, so we fuse them: while a
(bn, bk) tile of S is resident in VMEM for the Gram accumulation, the same
tile also accumulates its u contribution. S-traffic for the whole solve
drops from 3·n·m to 2·n·m words (the apply's re-read is unavoidable — it
needs w, which depends on the full Gram).

The u accumulation fires only on the j == 0 column of the output grid so
each (i, k) tile contributes exactly once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mosaic import dot_f32

__all__ = ["gram_sv_pallas"]


def _gram_sv_kernel(s_i_ref, s_j_ref, v_ref, w_ref, u_ref):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init_w():
        w_ref[...] = jnp.zeros_like(w_ref)

    a = s_i_ref[...]
    w_ref[...] += dot_f32(a, s_j_ref[...], ((1,), (1,)))

    # u tile (bn, 1): accumulate once per (i, k) — gate on j == 0.
    @pl.when(jnp.logical_and(j == 0, k == 0))
    def _init_u():
        u_ref[...] = jnp.zeros_like(u_ref)

    @pl.when(j == 0)
    def _acc_u():
        u_ref[...] += dot_f32(a, v_ref[...], ((1,), (0,)))


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def gram_sv_pallas(S: jax.Array, v: jax.Array, *, bn: int = 128,
                   bk: int = 512, interpret: bool = False):
    """Returns (W, u) = (S@S.T, S@v), both fp32. v is (m,) or (m, 1)."""
    n, m = S.shape
    assert n % bn == 0 and m % bk == 0, (n, m, bn, bk)
    squeeze = v.ndim == 1
    v2 = v[:, None] if squeeze else v
    grid = (n // bn, n // bn, m // bk)

    W, u = pl.pallas_call(
        _gram_sv_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
            pl.BlockSpec((bk, 1), lambda i, j, k: (k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((bn, 1), lambda i, j, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, n), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="gram_sv_fused",
    )(S, S, v2.astype(S.dtype))
    return W, (u[:, 0] if squeeze else u)
