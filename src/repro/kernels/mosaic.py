"""What the Pallas TPU (Mosaic) lowering asks of this package's kernels.

* ``dot_f32`` — Mosaic contracts fp32 operands in a single bf16 MXU pass
  unless told otherwise (on a v5e the S-pass kernels then missed their
  fp32 references by ~2e-3 relative). Every kernel contraction goes
  through here, so each matches ``kernels/ref.py``'s
  ``Precision.HIGHEST``. Two bf16 operands multiply exactly in one pass.
* ``pick_lane`` — Mosaic slices rows (the sublane axis) at a dynamic
  offset, never lanes: a dynamic column is an iota mask and a lane
  reduction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["dot_f32", "pick_lane"]


def dot_f32(a: jax.Array, b: jax.Array, contract) -> jax.Array:
    """``lax.dot_general`` over ``contract`` = (lhs dims, rhs dims), no
    batch dims, accumulated and returned in fp32 at fp32 accuracy."""
    dims = (contract, ((), ()))
    if a.dtype == b.dtype == jnp.bfloat16:
        return jax.lax.dot_general(a, b, dims,
                                   preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a.astype(jnp.float32), b.astype(jnp.float32),
                               dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def pick_lane(A: jax.Array, i) -> jax.Array:
    """A[:, i] as an (r, 1) column, for a dynamic lane index i."""
    cols = jax.lax.broadcasted_iota(jnp.int32, A.shape, 1)
    return jnp.sum(jnp.where(cols == i, A, 0.0), axis=1, keepdims=True)
