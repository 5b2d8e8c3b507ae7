"""Pallas TPU kernel: blocked in-VMEM Cholesky factorization  W = L·Lᵀ.

The paper's namesake op (its "chol" step). n is the *sample* count
(10²–10⁴), so the whole matrix fits VMEM for n ≤ ~1k fp32 — we factor it in
a single kernel invocation with a **right-looking row-panel algorithm** on
the upper factor U = Lᵀ (rows of U are columns of L, so every dynamic
access is a row slice — the sublane axis Mosaic indexes natively):

  for each panel k of width BP (a ``fori_loop``; the loop body is traced
  once):
    1. load the BP rows of the trailing matrix A (VMEM scratch, A = W
       minus every earlier panel's contribution);
    2. in-panel factorization — BP *unrolled* row steps of length-n
       vector ops (VPU): sqrt the pivot, scale right of the diagonal,
       zero left of it, and eliminate the row from the panel rows below;
       single entries are picked with an iota mask and a lane reduction;
    3. trailing update  A −= U_kᵀ·U_k — one (n × BP)·(BP × n) MXU matmul.

There is no triangular-solve primitive inside Pallas (lax.linalg does not
lower to Mosaic), which is exactly why the panel step is formulated as
masked vector arithmetic — the TPU-idiomatic replacement for cuSOLVER's
``potrf`` panel TRSM. Cost: n³ MXU FLOPs (vs n³/3 optimal — the trailing
update does not exploit symmetry) + O(n²·BP) VPU FLOPs; both are
negligible next to the O(n²·m) Gram since m ≫ n. The kernel writes U;
the wrapper hands back L = Uᵀ.

Larger n falls back to XLA's cholesky in ``ops.py`` (still n×n — tiny).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mosaic import dot_f32, pick_lane

__all__ = ["cholesky_pallas", "MAX_SINGLE_BLOCK_N"]

# W, U, the trailing matrix and one (n, n) matmul result in fp32: the v5e
# compile asks for ~12.6 MiB of VMEM at n=1024, inside the 16 MiB default
# scoped limit (tests/test_tpu_compile.py compiles this size).
MAX_SINGLE_BLOCK_N = 1024


def _chol_kernel(w_ref, u_ref, a_ref, *, panel: int):
    n = w_ref.shape[0]
    a_ref[...] = w_ref[...].astype(jnp.float32)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    prow = jax.lax.broadcasted_iota(jnp.int32, (panel, 1), 0)

    def panel_body(k, carry):
        col0 = pl.multiple_of(k * panel, panel)
        P = a_ref[pl.ds(col0, panel), :]                        # (BP, n)
        block = jnp.zeros((panel, n), jnp.float32)
        for j in range(panel):
            c = jnp.sum(jnp.where(prow == j, P, 0.0), axis=0,
                        keepdims=True)                          # (1, n)
            d = jnp.sqrt(jnp.maximum(pick_lane(c, col0 + j), 1e-30))
            row = jnp.where(cols > col0 + j, c / d, 0.0)
            row = jnp.where(cols == col0 + j, d, row)
            block = jnp.where(prow == j, row, block)
            # P[i] −= U[col0+j, col0+i] · row   for the panel rows i > j
            coef = jnp.sum(jnp.where(cols == col0 + prow, row, 0.0),
                           axis=1, keepdims=True)               # (BP, 1)
            P = P - jnp.where(prow > j, coef, 0.0) * row
        u_ref[pl.ds(col0, panel), :] = block
        a_ref[...] -= dot_f32(block, block, ((0,), (0,)))      # (n, n)
        return carry

    jax.lax.fori_loop(0, n // panel, panel_body, 0)


@functools.partial(jax.jit, static_argnames=("panel", "interpret"))
def cholesky_pallas(W: jax.Array, *, panel: int = 16,
                    interpret: bool = False) -> jax.Array:
    """Lower-triangular L with W = L@L.T. W must be SPD, n % panel == 0."""
    n = W.shape[0]
    assert W.shape == (n, n) and n % panel == 0, (W.shape, panel)
    U = pl.pallas_call(
        functools.partial(_chol_kernel, panel=panel),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],   # trailing A
        interpret=interpret,
        name="blocked_cholesky",
    )(W)
    return U.T
