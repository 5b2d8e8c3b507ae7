"""Pallas TPU kernels: the fused uniform-λ serve request path.

The serving hot path (``serve/server.py``) answers a k-request microbatch
at the resident damping λ₀ with Algorithm 1's cached-factor identity

    U = S·V ;  w = L⁻ᵀ L⁻¹ U ;  X = (V − Sᵀw) / λ₀

— two passes over the (n, m) score window plus n-sized triangular work.
Dispatched compositionally that is four XLA calls with the m-sized
intermediates U-producer/apply each re-negotiating HBM.  Here the whole
identity is ONE kernel invocation with a (2, m/bk) grid:

  phase 0 (cross pass): each (n, bk) tile of S accumulates its (S·V)ᵀ
    contribution into a (k, n) fp32 VMEM scratch that stays resident
    across the whole pass; on the last tile the forward/back triangular
    substitution against the resident L runs *in-kernel* (Mosaic has no
    triangular-solve primitive — it is the same masked row-by-row vector
    formulation as ``cholesky.py``'s panel step), leaving wᵀ in a second
    resident scratch.
  phase 1 (apply pass): S streams through VMEM a second time and each
    (k, bk) tile of Xᵀ = (Vᵀ − wᵀS)/λ₀ is written exactly once.

The kernel runs on Vᵀ and Xᵀ, so the requests lie along sublanes and the
RHS/solution tiles are lane-dense. XLA already stores a narrow (m, k)
array lane-dense, as its (k, m) transpose, so the wrapper's transposes
compile to bitcasts; (bk, k) tiles would instead make XLA copy V and X
into a row-major layout padded from k to 128 lanes (16× the bytes at
k=8: 36 GB at whisper-base's m=70.9 M, more than the chip holds). The
factor tile, RHS tiles and both (k, n) intermediates are pinned in VMEM
for the whole microbatch; accumulation is fp32 regardless of the window
storage dtype (bf16 windows upcast per-tile inside the kernel).

``sv_cross_pallas`` / ``serve_apply_pallas`` are the two S passes as
standalone kernels — the building blocks the blocked and sharded
(``repro.dist``, per-slab inside ``shard_map``) serve paths reuse when a
psum must sit between the cross pass and the substitution. They take V
and give X in the same lane-dense Vᵀ/Xᵀ tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mosaic import dot_f32, pick_lane

__all__ = ["serve_solve_pallas", "sv_cross_pallas", "serve_apply_pallas"]


def _trisolve(l_ref, Ut):
    """wᵀ for w = L⁻ᵀ L⁻¹ U by row-by-row substitution (no lax.linalg in
    Mosaic). l_ref: (n, n) lower-triangular factor in VMEM; Ut: (k, n) fp32,
    the right-hand sides laid out along lanes. Both sweeps read only row i
    of L (a dynamic sublane slice); entries at lane i are picked and set with
    an iota mask. O(n²k) VPU work in 2n sequential steps — negligible next
    to the O(n·m·k) passes."""
    k, n = Ut.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def fwd(i, Y):
        # lanes ≥ i of Y are still zero, so the full-row product only picks
        # up already-solved entries
        li = l_ref[pl.ds(i, 1), :].astype(jnp.float32)            # (1, n)
        acc = jnp.sum(Y * li, axis=1, keepdims=True)              # (k, 1)
        yi = (pick_lane(Ut, i) - acc) / pick_lane(li, i)
        return jnp.where(cols == i, yi, Y)

    Y = jax.lax.fori_loop(0, n, fwd, jnp.zeros_like(Ut))

    def bwd(t, carry):
        # right-looking: once w_i is known, y_r −= L[i, r]·w_i for r < i
        Y, Wt = carry
        i = n - 1 - t
        li = l_ref[pl.ds(i, 1), :].astype(jnp.float32)
        wi = pick_lane(Y, i) / pick_lane(li, i)
        return Y - wi * li, jnp.where(cols == i, wi, Wt)

    _, Wt = jax.lax.fori_loop(0, n, bwd, (Y, jnp.zeros_like(Ut)))
    return Wt


def _serve_solve_kernel(s_ref, l_ref, vt_ref, lam_ref, xt_ref, ut_ref, wt_ref):
    p = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(p == 0)
    def _cross():
        @pl.when(j == 0)
        def _init():
            ut_ref[...] = jnp.zeros_like(ut_ref)

        ut_ref[...] += dot_f32(vt_ref[...], s_ref[...],   # (k, n): contract bk
                               ((1,), (1,)))

        @pl.when(j == nj - 1)
        def _solve():
            wt_ref[...] = _trisolve(l_ref, ut_ref[...])

    @pl.when(p == 1)
    def _apply():
        wts = dot_f32(wt_ref[...], s_ref[...], ((1,), (0,)))  # (k, bk)
        inv_lam = 1.0 / lam_ref[0, 0]
        xt_ref[...] = (vt_ref[...] - wts) * inv_lam


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def serve_solve_pallas(S: jax.Array, L: jax.Array, V: jax.Array, lam,
                       *, bk: int = 512, interpret: bool = False) -> jax.Array:
    """X = (V − Sᵀ L⁻ᵀL⁻¹ S V)/λ.  S: (n, m); L: (n, n); V: (m, k) fp32.
    Returns (m, k) fp32. m % bk == 0 (zero pad is exact). The kernel runs
    on Vᵀ/Xᵀ, so its (k, bk) tiles are lane-dense."""
    n, m = S.shape
    k = V.shape[1]
    assert m % bk == 0, (m, bk)
    lam2 = jnp.asarray(lam, jnp.float32).reshape(1, 1)

    Xt = pl.pallas_call(
        _serve_solve_kernel,
        grid=(2, m // bk),
        in_specs=[
            pl.BlockSpec((n, bk), lambda p, j: (0, j)),
            pl.BlockSpec((n, n), lambda p, j: (0, 0)),
            pl.BlockSpec((k, bk), lambda p, j: (0, j)),
            pl.BlockSpec((1, 1), lambda p, j: (0, 0),
                         memory_space=pltpu.MemorySpace.SMEM),
        ],
        out_specs=pl.BlockSpec((k, bk), lambda p, j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((k, m), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((k, n), jnp.float32),     # resident Uᵀ = (S·V)ᵀ
            pltpu.VMEM((k, n), jnp.float32),     # resident wᵀ = (L⁻ᵀL⁻¹U)ᵀ
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="serve_solve_fused",
    )(S, L, V.astype(jnp.float32).T, lam2)
    return Xt.T


def _sv_cross_kernel(s_ref, vt_ref, u_ref):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        u_ref[...] = jnp.zeros_like(u_ref)

    u_ref[...] += dot_f32(s_ref[...], vt_ref[...], ((1,), (1,)))


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def sv_cross_pallas(S: jax.Array, V: jax.Array, *, bk: int = 512,
                    interpret: bool = False) -> jax.Array:
    """U = S @ V, fp32 accumulation into a single resident (n, k) tile.
    S: (n, m); V: (m, k), read as (k, bk) tiles of Vᵀ. m % bk == 0."""
    n, m = S.shape
    k = V.shape[1]
    assert m % bk == 0, (m, bk)
    return pl.pallas_call(
        _sv_cross_kernel,
        grid=(m // bk,),
        in_specs=[
            pl.BlockSpec((n, bk), lambda j: (0, j)),
            pl.BlockSpec((k, bk), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((n, k), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="serve_sv_cross",
    )(S, V.astype(jnp.float32).T)


def _serve_apply_kernel(s_ref, wt_ref, vt_ref, lam_ref, xt_ref):
    wts = dot_f32(wt_ref[...], s_ref[...], ((1,), (0,)))      # (k, bk)
    inv_lam = 1.0 / lam_ref[0, 0]
    xt_ref[...] = (vt_ref[...] - wts) * inv_lam


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def serve_apply_pallas(S: jax.Array, w: jax.Array, V: jax.Array, lam,
                       *, bk: int = 512, interpret: bool = False) -> jax.Array:
    """X = (V − Sᵀ @ w) / λ — the multi-RHS apply pass. S: (n, m);
    w: (n, k); V: (m, k). Returns (m, k) fp32. m % bk == 0. The kernel
    runs on Vᵀ/Xᵀ, as ``serve_solve_pallas`` does."""
    n, m = S.shape
    k = V.shape[1]
    assert m % bk == 0, (m, bk)
    lam2 = jnp.asarray(lam, jnp.float32).reshape(1, 1)
    Xt = pl.pallas_call(
        _serve_apply_kernel,
        grid=(m // bk,),
        in_specs=[
            pl.BlockSpec((n, bk), lambda j: (0, j)),
            pl.BlockSpec((k, n), lambda j: (0, 0)),
            pl.BlockSpec((k, bk), lambda j: (0, j)),
            pl.BlockSpec((1, 1), lambda j: (0, 0), memory_space=pltpu.MemorySpace.SMEM),
        ],
        out_specs=pl.BlockSpec((k, bk), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((k, m), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name="serve_apply",
    )(S, w.astype(jnp.float32).T, V.astype(jnp.float32).T, lam2)
    return Xt.T
