"""Pallas TPU kernel: fused NGD apply  x = (v − Sᵀ·w) / λ.

The second (and final) pass over S in Algorithm 1. Fusing the GEMV, the
subtraction and the 1/λ scale means each (bk,)-block of v / x crosses HBM
exactly once and the m-length intermediate Sᵀw never materializes.

Layout note: S is stored (n, m) — samples × parameters — so the contraction
for x is over the *sublane* axis of each (n, bk) tile: tile_out(bk, 1) =
tileᵀ(bk, n) · w(n, 1), expressed as dot_general contracting dim 0 of the
tile, which Mosaic maps to an MXU pass with the transposed operand. n must
fit a single block (n ≤ ~4k fp32 in 16 MB VMEM alongside the accumulator);
``ops.py`` enforces this and falls back to XLA beyond it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mosaic import dot_f32

__all__ = ["ngd_apply_pallas"]


def _ngd_apply_kernel(s_ref, w_ref, v_ref, lam_ref, x_ref):
    s = s_ref[...]                      # (n, bk)
    w = w_ref[...]                      # (n, 1)
    stw = dot_f32(s, w, ((0,), (0,)))   # (bk, 1) — contract the n axis
    inv_lam = 1.0 / lam_ref[0, 0]
    x_ref[...] = ((v_ref[...].astype(jnp.float32) - stw) * inv_lam
                  ).astype(x_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def ngd_apply_pallas(S: jax.Array, w: jax.Array, v: jax.Array, lam,
                     *, bk: int = 512, interpret: bool = False) -> jax.Array:
    """x = (v - S.T @ w) / lam.  S: (n, m); w: (n,); v: (m,). Returns (m,) f32."""
    n, m = S.shape
    assert m % bk == 0, (m, bk)
    lam2 = jnp.asarray(lam, jnp.float32).reshape(1, 1)
    w2 = w.reshape(n, 1).astype(jnp.float32)
    v2 = v.reshape(m, 1)

    x = pl.pallas_call(
        _ngd_apply_kernel,
        grid=(m // bk,),
        in_specs=[
            pl.BlockSpec((n, bk), lambda k: (0, k)),
            pl.BlockSpec((n, 1), lambda k: (0, 0)),
            pl.BlockSpec((bk, 1), lambda k: (k, 0)),
            pl.BlockSpec((1, 1), lambda k: (0, 0), memory_space=pltpu.MemorySpace.SMEM),
        ],
        out_specs=pl.BlockSpec((bk, 1), lambda k: (k, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name="ngd_apply",
    )(S, w2, v2, lam2)
    return x[:, 0]
