"""Pallas TPU kernel: fused fold cross-columns for the FIFO window update.

One online-adaptation fold (``serve/adapt.py``) replaces the k oldest
window samples and needs the new Gram columns

    cols   = S · rows†        (n, k)  — the only m-sized work of the fold
    corner = rows · rows†     (k, k)  — the replaced rows' own entries

before the 2k-core ``replace_factors`` split (which stays in XLA: its
2k×2k eigendecomposition has no Mosaic lowering, and it is m-free).
Compositionally those are two separate passes over ``rows``; fused, each
(n, bk) tile of S and (k, bk) tile of rows crosses HBM once and both
fp32 accumulators stay resident in VMEM across the whole m sweep —
regardless of the window storage dtype (bf16 tiles upcast on the MXU).

The rows must already be rounded to the window storage dtype when they
arrive (``serve/adapt.pad_to_window_cols`` is the single cast point), so
the columns describe exactly the values the FIFO write will store.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mosaic import dot_f32

__all__ = ["fold_cols_pallas"]


def _fold_cols_kernel(s_ref, r_ref, cols_ref, corner_ref):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        cols_ref[...] = jnp.zeros_like(cols_ref)
        corner_ref[...] = jnp.zeros_like(corner_ref)

    r = r_ref[...]
    cols_ref[...] += dot_f32(s_ref[...], r, ((1,), (1,)))
    corner_ref[...] += dot_f32(r, r, ((1,), (1,)))


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def fold_cols_pallas(S: jax.Array, rows: jax.Array, *, bk: int = 512,
                     interpret: bool = False):
    """(cols, corner) = (S @ rowsᵀ, rows @ rowsᵀ), both fp32.
    S: (n, m); rows: (k, m). m % bk == 0 (zero pad is exact)."""
    n, m = S.shape
    k = rows.shape[0]
    assert rows.shape[1] == m and m % bk == 0, (S.shape, rows.shape, bk)
    return pl.pallas_call(
        _fold_cols_kernel,
        grid=(m // bk,),
        in_specs=[
            pl.BlockSpec((n, bk), lambda j: (0, j)),
            pl.BlockSpec((k, bk), lambda j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((n, k), lambda j: (0, 0)),
            pl.BlockSpec((k, k), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, k), jnp.float32),
            jax.ShapeDtypeStruct((k, k), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="fold_cols_fused",
    )(S, rows)
