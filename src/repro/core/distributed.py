"""Distributed Algorithm 1 under ``shard_map``.

The paper (§3) notes the algorithm "can share the same parallelization
strategy" as RVB+23's supplement. We make that strategy first-class and
jax-native:

* **Model-axis (parameter) sharding** — each device holds the local slab
  ``S_loc : (n, m_loc)``. The n×n Gram is the psum of local Grams; the tiny
  Cholesky + triangular solves are *replicated* (O(n³) ≪ O(n²·m_loc)); the
  apply ``x_loc = (v_loc − S_locᵀ w)/λ`` is embarrassingly local. Collective
  cost per solve: one psum of n² + one psum of n·k floats.

* **Data-axis (sample) sharding** — S is additionally split over rows. Each
  device all-gathers the *sample* axis of its (n_loc, m_loc) slab (cheap:
  n·m_loc words), then proceeds as above. Used when n is itself large
  (e.g. SR with 16k walkers).

The public entry points close over a mesh and axis names and are designed to
be called *inside* an outer pjit/shard_map training step or standalone.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.shard_compat import shard_map_compat as _shard_map

__all__ = [
    "sharded_chol_solve",
    "sharded_chol_solve_2d",
    "sharded_blocked_chol_solve",
    "make_sharded_solver",
]


def _dual_solve_local(S_loc: jax.Array, v_loc: jax.Array, lam,
                      *, model_axis: str, extra_sum_axes: tuple[str, ...] = ()):
    """Core of Algorithm 1 with S sharded over the parameter axis.

    Runs inside shard_map. ``extra_sum_axes`` lets the Gram psum also reduce
    over additional mesh axes (e.g. the 'pod' axis in multi-pod meshes when
    parameters are sharded over pods too).
    """
    axes = (model_axis,) + tuple(extra_sum_axes)
    n = S_loc.shape[0]
    acc = jnp.promote_types(S_loc.dtype, jnp.float32)
    S32 = S_loc.astype(acc)
    v32 = v_loc.astype(acc)

    # Local Gram & local Sv — one psum each (the only collectives here).
    W = jax.lax.psum(
        jnp.matmul(S32, S32.T, precision=jax.lax.Precision.HIGHEST), axes)
    u = jax.lax.psum(
        jnp.matmul(S32, v32, precision=jax.lax.Precision.HIGHEST), axes)

    W = W + jnp.asarray(lam, acc) * jnp.eye(n, dtype=acc)
    L = jnp.linalg.cholesky(W)          # replicated: n×n on every device
    w = solve_triangular(L, u, lower=True)
    w = solve_triangular(L.T, w, lower=False)
    x_loc = (v32 - jnp.matmul(S32.T, w, precision=jax.lax.Precision.HIGHEST)) \
        / jnp.asarray(lam, acc)
    return x_loc.astype(v_loc.dtype)


def sharded_chol_solve(S: jax.Array, v: jax.Array, damping, *,
                       mesh: Mesh,
                       model_axis: str = "model",
                       extra_sum_axes: tuple[str, ...] = ()) -> jax.Array:
    """Algorithm 1 with S (n, m) sharded over ``model_axis`` columns.

    ``v`` is sharded identically on its (single) parameter axis; the result
    carries the same sharding, so the optimizer applies it with zero
    re-sharding traffic.
    """
    fn = _shard_map(
        functools.partial(_dual_solve_local, model_axis=model_axis,
                          extra_sum_axes=extra_sum_axes),
        mesh=mesh,
        in_specs=(P(None, model_axis), P(model_axis), P()),
        out_specs=P(model_axis),
    )
    return fn(S, v, jnp.asarray(damping))


def _dual_solve_local_2d(S_loc: jax.Array, v_loc: jax.Array, lam, *,
                         data_axis: str, model_axis: str,
                         extra_sum_axes: tuple[str, ...] = ()):
    """2-D sharded variant: S is (n, m) sharded (data, model).

    all_gather over the *sample* axis first (cheap: n × m_loc words), then
    the 1-D path. After the gather every data-rank within a column group
    holds an identical row-complete slab, so the Gram psum reduces over the
    *model* axis only (reducing over data too would double-count).
    """
    S_cols = jax.lax.all_gather(S_loc, data_axis, axis=0, tiled=True)
    return _dual_solve_local(S_cols, v_loc, lam, model_axis=model_axis,
                             extra_sum_axes=tuple(extra_sum_axes))


def sharded_chol_solve_2d(S: jax.Array, v: jax.Array, damping, *,
                          mesh: Mesh,
                          data_axis: str = "data",
                          model_axis: str = "model",
                          extra_sum_axes: tuple[str, ...] = ()) -> jax.Array:
    """Algorithm 1 with S sharded (samples → data axis, params → model axis).

    ``v`` (and the returned x) are sharded over the model axis and
    replicated over data — exactly the layout of gradient buffers in a
    DP×TP trainer, so no re-sharding traffic on either side of the solve.
    """
    fn = _shard_map(
        functools.partial(_dual_solve_local_2d, data_axis=data_axis,
                          model_axis=model_axis, extra_sum_axes=extra_sum_axes),
        mesh=mesh,
        in_specs=(P(data_axis, model_axis), P(model_axis), P()),
        out_specs=P(model_axis),
    )
    return fn(S, v, jnp.asarray(damping))


def _blocked_dual_solve_local(S_op, v_blocks, lam, *, model_axis: str,
                              extra_sum_axes: tuple[str, ...] = ()):
    """Blocked Algorithm 1 inside shard_map: every block (n, m_b) is a
    column-sharded slab; the local Gram accumulates over the device's slab
    of *every* block before the single n² psum, so collective cost is
    identical to the dense path (one psum of n² + one of n·k) while no
    flat (n, m) array exists on any device.
    """
    axes = (model_axis,) + tuple(extra_sum_axes)
    n = S_op.n
    acc = jnp.promote_types(S_op.dtype, jnp.float32)
    S32 = S_op.astype(acc)
    v32 = jax.tree.map(lambda b: b.astype(acc), tuple(v_blocks))

    # Accumulate across local blocks first (fp32), then one psum each.
    W = jax.lax.psum(S32.gram(mode="real"), axes)
    u = jax.lax.psum(S32.matvec(v32), axes)

    W = W + jnp.asarray(lam, acc) * jnp.eye(n, dtype=acc)
    L = jnp.linalg.cholesky(W)          # replicated: n×n on every device
    w = solve_triangular(L, u, lower=True)
    w = solve_triangular(L.T, w, lower=False)
    y = S32.rmatvec(w)
    inv_lam = 1.0 / jnp.asarray(lam, acc)
    return jax.tree.map(
        lambda vb, yb, v0: ((vb - yb) * inv_lam).astype(v0.dtype),
        v32, tuple(y), tuple(v_blocks))


def sharded_blocked_chol_solve(S, v_blocks, damping, *,
                               mesh: Mesh,
                               model_axis: str = "model",
                               extra_sum_axes: tuple[str, ...] = ()):
    """Algorithm 1 on a ``BlockedScores`` operator whose blocks are each
    sharded over ``model_axis`` columns (the per-layer analogue of
    ``sharded_chol_solve``). ``v_blocks`` is the matching tuple of
    per-block right-hand sides; the result keeps block structure and
    sharding, so a per-layer optimizer applies it with zero re-sharding.

    Consume the result per block (elementwise / gather): concatenating
    would defeat the blocked representation.
    """
    from repro.core.operator import BlockedScores, LazyBlockedScores

    if isinstance(S, LazyBlockedScores):
        S = S.materialize()
    if not isinstance(S, BlockedScores):
        raise TypeError("sharded_blocked_chol_solve needs a BlockedScores; "
                        "use sharded_chol_solve for dense S")
    v_blocks = tuple(v_blocks)
    # P specs are pytree prefixes: one spec broadcasts over every block.
    fn = _shard_map(
        functools.partial(_blocked_dual_solve_local, model_axis=model_axis,
                          extra_sum_axes=extra_sum_axes),
        mesh=mesh,
        in_specs=(P(None, model_axis), P(model_axis), P()),
        out_specs=P(model_axis),
    )
    return fn(S, v_blocks, jnp.asarray(damping))


def make_sharded_solver(mesh: Mesh, *, layout: str = "1d",
                        data_axis: str = "data", model_axis: str = "model",
                        extra_sum_axes: tuple[str, ...] = ()):
    """Return ``solve(S, v, λ) -> x`` closed over a mesh/sharding layout.

    layout="1d": S sharded over params only (the RVB+23 strategy).
    layout="2d": S sharded over (samples, params).
    layout="blocked": per-layer BlockedScores, each block column-sharded.
    """
    if layout == "blocked":
        return functools.partial(sharded_blocked_chol_solve, mesh=mesh,
                                 model_axis=model_axis,
                                 extra_sum_axes=extra_sum_axes)
    if layout == "1d":
        return functools.partial(sharded_chol_solve, mesh=mesh,
                                 model_axis=model_axis,
                                 extra_sum_axes=extra_sum_axes)
    if layout == "2d":
        return functools.partial(sharded_chol_solve_2d, mesh=mesh,
                                 data_axis=data_axis, model_axis=model_axis,
                                 extra_sum_axes=extra_sum_axes)
    raise ValueError(f"unknown layout {layout!r}")
