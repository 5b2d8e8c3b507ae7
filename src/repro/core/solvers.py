"""Solvers for the damped natural-gradient linear system  (SᵀS + λI) x = v.

This module is the paper's core contribution (Algorithm 1) plus every
baseline it benchmarks against:

* ``chol_solve``   — Algorithm 1 (Cholesky in the n-dimensional dual space).
* ``eigh_solve``   — Appendix C "eigh": eigendecomposition of S·Sᵀ.
* ``svd_solve``    — Appendix C "svda": thin SVD of S (XLA SVD on TPU).
* ``cg_solve``     — matrix-free conjugate gradient (the iterative baseline
  discussed in §3).
* ``direct_solve`` — naive O(m³) solve of the m×m system (small-m oracle).
* ``minsr_solve``  — RVB+23 ``x = Sᵀ(SSᵀ+λĨ)⁻¹f`` for the restricted case
  ``v = Sᵀf`` (Appendix B equivalence).

All solvers share the signature ``solve(S, v, damping, **kw) -> x`` where
``S`` is either the dense (n, m) score matrix with m ≫ n **or** a blocked
operator (``repro.core.operator.BlockedScores`` / ``LazyBlockedScores``)
holding per-layer (n, m_b) blocks that are never concatenated. With a
blocked S, the right-hand side ``v`` may be a flat (m,) / (m, k) array or
a tuple of per-block pieces; the solution comes back in the same form.

``chol_solve`` is a thin wrapper over ``chol_factorize`` →
``CholFactorization``: the O(n²·m) Gram pass and O(n³) Cholesky are done
once and the resulting object serves any number of right-hand sides
(``.solve``) and re-dampings (``.with_damping`` — reuses the cached
undamped Gram, so changing λ costs O(n³), not another pass over S).

Complex stochastic-reconfiguration variants are handled per the paper's §3:

* ``mode="complex"``   — Hermitian Fisher F = S†S; transposes become
  conjugate-transposes throughout; x may be complex.
* ``mode="real_part"`` — F = Re[S†S]; S is replaced by
  ``concat([Re S, Im S])`` along the sample axis and the real algorithm
  runs unchanged.
* ``mode="real"``      — plain real algorithm (default for real S).
"""
from __future__ import annotations

import functools
from typing import Callable, Literal, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from repro.core.operator import (
    BlockedScores,
    LazyBlockedScores,
    ScoreOperator,
    as_blocked_vector,
    block_norm,
    is_blocked,
)

Mode = Literal["auto", "real", "complex", "real_part"]

__all__ = [
    "chol_solve",
    "chol_factorize",
    "CholFactorization",
    "eigh_solve",
    "svd_solve",
    "cg_solve",
    "direct_solve",
    "minsr_solve",
    "center_scores",
    "gram",
    "gram_chunked",
    "SOLVERS",
    "get_solver",
    "SolverStats",
]

_HI = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# helpers (dense-or-operator uniform)
# ---------------------------------------------------------------------------

def _resolve_mode(S, mode: Mode) -> str:
    if mode == "auto":
        return "complex" if jnp.issubdtype(S.dtype, jnp.complexfloating) \
            else "real"
    return mode


def _realify(S, v, mode: str):
    """Apply the paper's real-part SR transform: S ← [Re S; Im S]."""
    if mode == "real_part" and jnp.issubdtype(S.dtype, jnp.complexfloating):
        S = S.realify() if is_blocked(S) else \
            jnp.concatenate([jnp.real(S), jnp.imag(S)], axis=0)
        v = jax.tree.map(
            lambda b: jnp.real(b)
            if jnp.issubdtype(b.dtype, jnp.complexfloating) else b, v)
        return S, v, "real"
    return S, v, mode


def _ct(A: jax.Array, mode: str) -> jax.Array:
    """Transpose, or conjugate-transpose in complex mode."""
    return A.conj().T if mode == "complex" else A.T


def _promote(S, v):
    """Upcast sub-fp32 inputs for the dual-space math (Cholesky/eigh/SVD
    have no bf16 kernels; the convert fuses into the Gram matmul, so S's
    HBM traffic stays bf16)."""
    tgt = jnp.promote_types(S.dtype, jnp.float32)
    vt = jax.tree.map(
        lambda b: b.astype(jnp.promote_types(b.dtype, tgt)), v)
    return S.astype(tgt), vt


def _prepare(S, v, mode: Mode):
    """mode-resolve → realify → promote, dense or blocked. Lazy operators
    are materialized here (first contraction is about to happen anyway)."""
    if isinstance(S, LazyBlockedScores):
        S = S.materialize()
    mode = _resolve_mode(S, mode)
    S, v, mode = _realify(S, v, mode)
    S, v = _promote(S, v)
    return S, v, mode


def _op_gram(S, *, mode: str, precision=_HI) -> jax.Array:
    if is_blocked(S):
        return S.gram(mode=mode, precision=precision)
    return gram(S, mode=mode, precision=precision)


def _op_matvec(S, v, *, precision=_HI) -> jax.Array:
    """u = S·v — v flat, (m, k), or blocked when S is an operator."""
    if is_blocked(S):
        return S.matvec(v, precision=precision)
    return jnp.matmul(S, v, precision=precision)


def _op_rmatvec(S, w, *, mode: str, precision=_HI):
    """y = Sᵀ·w — blocked result when S is an operator."""
    if is_blocked(S):
        return S.rmatvec(w, mode=mode, precision=precision)
    return jnp.matmul(_ct(S, mode), w, precision=precision)


def center_scores(O: jax.Array, *, weights: Optional[jax.Array] = None) -> jax.Array:
    """SR centering: S = (O − Ō)/√n  (paper §3).

    ``O[i, j] = ∂ log ψ(x_i)/∂θ_j``; optional per-sample probability weights
    (must sum to 1) for weighted estimators.
    """
    n = O.shape[0]
    if weights is None:
        mean = jnp.mean(O, axis=0, keepdims=True)
        return (O - mean) / jnp.sqrt(n).astype(O.real.dtype)
    mean = jnp.sum(weights[:, None] * O, axis=0, keepdims=True)
    return jnp.sqrt(weights)[:, None] * (O - mean)


_SYRK_PANELS = 8        # block columns the panel width aims for


@functools.partial(jax.jit, static_argnames=("mode", "precision"))
def _gram_syrk(S: jax.Array, *, mode: str, precision) -> jax.Array:
    """Lower block triangle of S·Sᵀ (S·S†), one matmul per block column
    over the parameter axis, mirrored into a full, exactly symmetric
    (Hermitian) W. One program, so the row slices of S feed the dots and
    are never copied; each column is written into W in place (an update
    slice compiles for the TPU in ~3 s less than padding and
    concatenating the columns)."""
    n = S.shape[0]
    # a multiple of 128 rows giving ~8 block columns: 256 at n = 2048,
    # the fastest of 128–1024 there on a TPU v5e; one panel for n ≤ 128
    panel = min(n, 128 * max(1, -(-n // (128 * _SYRK_PANELS))))
    with jax.named_scope("gram_syrk"):
        Wl = jnp.zeros((n, n), S.dtype)
        for j0 in range(0, n, panel):
            P = S[j0:j0 + panel]
            if mode == "complex":
                P = P.conj()
            C = jax.lax.dot_general(S[j0:], P, (((1,), (1,)), ((), ())),
                                    precision=precision)
            Wl = jax.lax.dynamic_update_slice(Wl, C, (j0, j0))
        r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        W = jnp.where(r > c, Wl, _ct(Wl, mode))
        if mode == "complex":
            W = jnp.where(r == c, W.real.astype(W.dtype), W)
    return W


def gram(S, *, mode: str = "real", precision=_HI) -> jax.Array:
    """W = S·Sᵀ (or S·S† in complex mode) at ``precision``, fp32/fp64
    accumulation.

    A dense (n, m) S is a symmetric rank-k product: only the lower block
    triangle is computed, in ⌈n/b⌉ panels of b rows (b a multiple of 128
    giving ~8 block columns, 56% of the full product's multiply-adds at
    n = 2048; a single panel, one full dot, for n ≤ 128), and mirrored,
    so W is exactly symmetric (Hermitian with a real diagonal). A blocked
    operator accumulates block-wise, with no concatenation."""
    if is_blocked(S):
        return S.gram(mode=mode, precision=precision)
    return _gram_syrk(S, mode=mode, precision=precision)


def gram_chunked(S: jax.Array, chunk: int, *, mode: str = "real",
                 precision=_HI) -> jax.Array:
    """W = S·Sᵀ accumulated over parameter-axis chunks of width ``chunk``.

    Bounds the transient memory of mixed-precision upcasts when S is stored
    in bf16 but accumulated in fp32: peak extra memory is O(n·chunk), not
    O(n·m). The loop is a ``lax.scan`` so the HLO stays O(1) in m. A
    blocked operator is already chunk-shaped; it routes to block-wise
    accumulation directly.
    """
    if is_blocked(S):
        return S.gram(mode=mode, precision=precision)
    n, m = S.shape
    nchunks = -(-m // chunk)
    pad = nchunks * chunk - m
    if pad:
        S = jnp.pad(S, ((0, 0), (0, pad)))
    Sb = S.reshape(n, nchunks, chunk).transpose(1, 0, 2)  # (nchunks, n, chunk)

    acc_dtype = jnp.promote_types(S.dtype, jnp.float32)

    def body(acc, Sc):
        Sc = Sc.astype(acc_dtype)
        return acc + jnp.matmul(Sc, _ct(Sc, mode), precision=precision), None

    W0 = jnp.zeros((n, n), dtype=acc_dtype if mode != "complex"
                   else jnp.promote_types(S.dtype, jnp.complex64))
    W, _ = jax.lax.scan(body, W0, Sb)
    return W


class SolverStats(NamedTuple):
    """Diagnostics returned by ``chol_solve(..., return_stats=True)`` and
    ``CholFactorization.solve(..., return_stats=True)``."""
    residual_norm: jax.Array      # ‖(SᵀS+λI)x − v‖ / ‖v‖
    gram_cond_proxy: jax.Array    # max/min diagonal of W + λĨ (cheap proxy)


def residual(S, v, x, damping, *, mode: str = "real") -> jax.Array:
    """Relative residual of the damped system — used by tests & benchmarks.

    Dense or blocked; with a blocked S, ``v``/``x`` may be flat or blocked.
    """
    if is_blocked(S):
        v_blocks, _ = as_blocked_vector(S, v)
        x_blocks, _ = as_blocked_vector(S, x)
        y = S.rmatvec(S.matvec(x_blocks), mode=mode)
        lam = jnp.asarray(damping)
        r = jax.tree.map(lambda yb, xb, vb: yb + lam * xb - vb,
                         tuple(y), tuple(x_blocks), tuple(v_blocks))
        return block_norm(r) / block_norm(v_blocks)
    Ax = _ct(S, mode) @ (S @ x) + damping * x
    return jnp.linalg.norm(Ax - v) / jnp.linalg.norm(v)


# ---------------------------------------------------------------------------
# Algorithm 1 — the paper's contribution
# ---------------------------------------------------------------------------

class CholFactorization:
    """Reusable Cholesky factorization of the dual system (Algorithm 1).

    Produced by ``chol_factorize``. Holds the prepared S (dense or
    blocked), the *undamped* Gram W, and the Cholesky factor L of
    W + (λ+jitter)Ĩ, so that:

    * ``solve(v)`` costs two passes over S + two n×n triangular solves —
      any number of right-hand sides amortize the factorization;
    * ``with_damping(λ')`` re-factors the cached n×n W at O(n³) without
      touching S again — the multi-λ pattern of trust-region damping
      schedules and λ line-searches.
    """

    def __init__(self, *, S, mode: str, W: jax.Array, L: jax.Array,
                 lam: jax.Array, jitter: float, take_real_v: bool,
                 precision):
        self.S = S                      # prepared: realified + promoted
        self.mode = mode                # resolved: "real" | "complex"
        self.W = W                      # undamped Gram (n, n)
        self.L = L                      # chol(W + (λ+jitter)Ĩ)
        self.lam = lam
        self.jitter = jitter
        self._take_real_v = take_real_v
        self.precision = precision

    @property
    def n(self) -> int:
        return self.W.shape[0]

    def with_damping(self, damping, *, jitter: Optional[float] = None
                     ) -> "CholFactorization":
        """New factorization at a different λ, reusing the cached Gram."""
        jit_ = self.jitter if jitter is None else jitter
        lam = jnp.asarray(damping, dtype=self.W.real.dtype)
        Wd = self.W + (lam + jit_) * jnp.eye(self.n, dtype=self.W.dtype)
        L = jnp.linalg.cholesky(Wd)
        return CholFactorization(S=self.S, mode=self.mode, W=self.W, L=L,
                                 lam=lam, jitter=jit_,
                                 take_real_v=self._take_real_v,
                                 precision=self.precision)

    def _replace(self, *, S, W, L) -> "CholFactorization":
        return CholFactorization(S=S, mode=self.mode, W=W, L=L,
                                 lam=self.lam, jitter=self.jitter,
                                 take_real_v=self._take_real_v,
                                 precision=self.precision)

    def update(self, cols, *, S_new=None) -> "CholFactorization":
        """Rank-k streaming refresh: fold k new score columns into the
        factorization at O(n²·k) — no Gram pass, no re-factorization.

        ``cols`` (n, k) are new columns of the *prepared* S (dual-space
        vectors: new parameters' scores, a microbatch's contribution, the
        update half of a sliding window — see ``repro.curvature``):

            W ← W + cols·cols†;   L ← cholupdate(L, cols)

        By default ``cols`` is also appended to the held S (a new block
        for a blocked operator), keeping ``solve`` exact for the grown
        system; pass ``S_new`` to substitute a different operator (e.g.
        when the columns replace rather than extend — the caller owns
        S/W consistency then, as ``CurvatureCache`` does).
        """
        from repro.kernels.ops import cholupdate as _cholupdate
        cols = jnp.asarray(cols)
        if cols.ndim == 1:
            cols = cols[:, None]
        cols = cols.astype(self.S.dtype)
        W = self.W + jnp.matmul(cols, _ct(cols, self.mode),
                                precision=self.precision)
        L = _cholupdate(self.L, cols, sign=+1)
        if S_new is None:
            S_new = BlockedScores(self.S.blocks + (cols,),
                                  names=None) if is_blocked(self.S) else \
                jnp.concatenate([self.S, cols], axis=1)
        return self._replace(S=S_new, W=W, L=L)

    def downdate(self, cols, *, S_new=None) -> "CholFactorization":
        """Rank-k removal — the inverse of ``update`` at the same O(n²·k):

            W ← W − cols·cols†;   L ← choldowndate(L, cols)

        ``W − cols·cols†`` must stay PSD (true whenever the columns are
        actually present in S, e.g. a retiring block of a sliding window).
        Removing columns from S is not inferable from their values, so
        ``S_new`` names the shrunken operator; when omitted, S is kept
        as-is and ``solve`` becomes the *stale-S* approximation that
        ``CurvatureCache`` monitors via ``residual``.
        """
        from repro.kernels.ops import cholupdate as _cholupdate
        cols = jnp.asarray(cols)
        if cols.ndim == 1:
            cols = cols[:, None]
        cols = cols.astype(self.S.dtype)
        W = self.W - jnp.matmul(cols, _ct(cols, self.mode),
                                precision=self.precision)
        L = _cholupdate(self.L, cols, sign=-1)
        return self._replace(S=self.S if S_new is None else S_new, W=W, L=L)

    def _prep_v(self, v):
        if self._take_real_v:
            v = jax.tree.map(
                lambda b: jnp.real(b)
                if jnp.issubdtype(b.dtype, jnp.complexfloating) else b, v)
        tgt = jnp.promote_types(self.S.dtype, jnp.float32)
        return jax.tree.map(
            lambda b: b.astype(jnp.promote_types(b.dtype, tgt)), v)

    def solve(self, v, *, return_stats: bool = False):
        """x = (SᵀS + λI)⁻¹ v via the paper's dual-space identity:

            u = S v ;  w = L⁻ᵀ L⁻¹ u ;  x = (v − Sᵀ w) / λ
        """
        blocked = is_blocked(self.S)
        if blocked:
            v_in, was_flat = as_blocked_vector(self.S, v)
            v_in = self._prep_v(v_in)
        else:
            v_in, was_flat = self._prep_v(v), True

        u = _op_matvec(self.S, v_in, precision=self.precision)
        w = solve_triangular(self.L, u, lower=True)
        w = solve_triangular(_ct(self.L, self.mode), w, lower=False)
        y = _op_rmatvec(self.S, w, mode=self.mode, precision=self.precision)
        if blocked:
            x = jax.tree.map(lambda vb, yb: (vb - yb) / self.lam,
                             tuple(v_in), tuple(y))
            x_out = BlockedScores.concat(x) if was_flat else x
        else:
            x = (v_in - y) / self.lam
            x_out = x

        if not return_stats:
            return x_out
        r = residual(self.S, v_in, x, self.lam, mode=self.mode)
        diag = jnp.real(jnp.diagonal(self.W)) + self.lam + self.jitter
        stats = SolverStats(residual_norm=r,
                            gram_cond_proxy=jnp.max(diag) / jnp.min(diag))
        return x_out, stats

    def solve_batch(self, V, dampings, *, jitter: Optional[float] = None):
        """x_j = (SᵀS + λ_j I)⁻¹ v_j — a coalesced batch of right-hand
        sides with **per-column** damping, in one pass over S each way.

        The serving-path workhorse: k requests with individual λ share the
        cached undamped Gram W, so the m-sized work stays batched —

            U = S·V                                  (one O(n·m·k) pass)
            L_j = chol(W + (λ_j + jitter)·Ĩ)         (batched, O(k·n³))
            w_j = L_j⁻ᵀ L_j⁻¹ u_j                    (batched triangular)
            Y = Sᵀ·[w_1 … w_k]                       (one O(n·m·k) pass)
            x_j = (v_j − y_j) / λ_j

        — against k separate ``with_damping(λ_j).solve(v_j)`` calls, which
        would pay the two S passes per request. ``V`` is (m, k) (or a tuple
        of per-block (m_b, k) pieces for a blocked S; blocked in → blocked
        out); ``dampings`` is (k,). With all λ equal this matches
        ``with_damping(λ).solve(V)`` column for column.
        """
        jit_ = self.jitter if jitter is None else jitter
        blocked = is_blocked(self.S)
        if blocked:
            v_in, was_flat = as_blocked_vector(self.S, V)
            v_in = self._prep_v(v_in)
            k = v_in[0].shape[1]
        else:
            v_in, was_flat = self._prep_v(V), True
            if v_in.ndim != 2:
                raise ValueError(
                    f"solve_batch takes an (m, k) batch of RHS columns, "
                    f"got shape {v_in.shape}")
            k = v_in.shape[1]
        lams = jnp.asarray(dampings, dtype=self.W.real.dtype).reshape(-1)
        if lams.shape[0] != k:
            raise ValueError(f"{lams.shape[0]} dampings for {k} RHS columns")

        eye = jnp.eye(self.n, dtype=self.W.dtype)
        Wd = self.W[None] + (lams + jit_)[:, None, None] * eye    # (k, n, n)
        Ls = jnp.linalg.cholesky(Wd)
        u = _op_matvec(self.S, v_in, precision=self.precision)    # (n, k)
        ut = u.T[..., None]                                       # (k, n, 1)
        w = jax.vmap(lambda L, b: solve_triangular(L, b, lower=True))(Ls, ut)
        w = jax.vmap(lambda L, b: solve_triangular(
            _ct(L, self.mode), b, lower=False))(Ls, w)
        w = w[..., 0].T                                           # (n, k)
        y = _op_rmatvec(self.S, w, mode=self.mode, precision=self.precision)
        if blocked:
            x = jax.tree.map(lambda vb, yb: (vb - yb) / lams[None, :],
                             tuple(v_in), tuple(y))
            return BlockedScores.concat(x) if was_flat else x
        return (v_in - y) / lams[None, :]


def chol_factorize(S, damping, *,
                   mode: Mode = "auto",
                   gram_chunk: Optional[int] = None,
                   gram_fn: Optional[Callable] = None,
                   W: Optional[jax.Array] = None,
                   jitter: float = 0.0,
                   precision=_HI) -> CholFactorization:
    """Run the O(n²·m) + O(n³) setup of Algorithm 1 once; see
    ``CholFactorization`` for what the returned object amortizes.

    ``W``: optional precomputed *undamped* Gram of the prepared (realified,
    promoted) S — skips the O(n²·m) pass entirely. This is the reuse hook
    of the streaming-curvature subsystem: ``StreamingGram`` accumulates W
    over microbatches and ``CurvatureCache`` carries it across steps.
    """
    orig_complex = jnp.issubdtype(S.dtype, jnp.complexfloating)
    resolved = _resolve_mode(S, mode)
    take_real_v = (resolved == "real_part" and orig_complex)
    # realify/promote S only; v is handled per-solve.
    if isinstance(S, LazyBlockedScores):
        S = S.materialize()
    if take_real_v:
        S = S.realify() if is_blocked(S) else \
            jnp.concatenate([jnp.real(S), jnp.imag(S)], axis=0)
        resolved = "real"
    S = S.astype(jnp.promote_types(S.dtype, jnp.float32))

    n = S.shape[0]
    if W is not None:
        W = jnp.asarray(W)
        if W.shape != (n, n):
            raise ValueError(f"precomputed Gram is {W.shape}, prepared S "
                             f"needs ({n}, {n})")
    elif gram_fn is not None and not is_blocked(S):
        W = gram_fn(S)
    elif gram_chunk is not None and not is_blocked(S):
        W = gram_chunked(S, gram_chunk, mode=resolved, precision=precision)
    else:
        W = _op_gram(S, mode=resolved, precision=precision)
    lam = jnp.asarray(damping, dtype=W.real.dtype)
    Wd = W + (lam + jitter) * jnp.eye(n, dtype=W.dtype)
    L = jnp.linalg.cholesky(Wd)
    return CholFactorization(S=S, mode=resolved, W=W, L=L, lam=lam,
                             jitter=jitter, take_real_v=take_real_v,
                             precision=precision)


def chol_solve(S, v, damping, *,
               mode: Mode = "auto",
               gram_chunk: Optional[int] = None,
               gram_fn: Optional[Callable] = None,
               jitter: float = 0.0,
               return_stats: bool = False,
               precision=_HI):
    """Algorithm 1: solve (SᵀS + λI) x = v via Cholesky of the n×n Gram.

    Steps (with the paper's line-4 inlining note applied — Q = L⁻¹S is never
    materialized; the apply is two triangular solves on n-vectors):

        W = S Sᵀ + λ Ĩ
        L = chol(W)
        u = S v
        w = L⁻ᵀ (L⁻¹ u)
        x = (v − Sᵀ w) / λ

    Args:
      S: (n, m) score matrix (real or complex), or a blocked operator.
      v: (m,) or (m, k) right-hand side(s); with a blocked S also a tuple
        of per-block pieces (the result then comes back blocked too).
      damping: λ > 0.
      mode: "auto" | "real" | "complex" | "real_part" (see module docstring).
      gram_chunk: if set, accumulate the Gram matrix in parameter chunks
        (dense S only; a blocked S is inherently chunk-accumulated).
      gram_fn: optional override (e.g. the Pallas ``gram`` kernel).
      jitter: extra diagonal added to W for numerical safety (0 = faithful).
      return_stats: if True, return ``(x, SolverStats)`` where the stats
        carry the relative residual and a cheap Gram condition proxy.
    """
    fac = chol_factorize(S, damping, mode=mode, gram_chunk=gram_chunk,
                         gram_fn=gram_fn, jitter=jitter, precision=precision)
    return fac.solve(v, return_stats=return_stats)


# ---------------------------------------------------------------------------
# Appendix C baselines
# ---------------------------------------------------------------------------

def eigh_solve(S, v, damping, *,
               mode: Mode = "auto",
               eps: float = 1e-12,
               precision=_HI):
    """Appendix C "eigh": SVD of S via eigendecomposition of S·Sᵀ.

        S Sᵀ = U Σ² Uᵀ ;  V = Sᵀ U Σ⁻¹
        x = V (Σ² + λ)⁻¹ Vᵀ v + (v − V Vᵀ v)/λ

    Previously the fastest method in the authors' experience; our reference
    competitor. Small/negative eigenvalues are clamped at ``eps`` before the
    inverse square root (rank-deficiency guard), matching standard practice.
    Blocked operators run the same math with block-wise Sᵀ applies.
    """
    blocked = is_blocked(S)
    was_flat = True
    if blocked:
        if isinstance(S, LazyBlockedScores):
            S = S.materialize()
        v, was_flat = as_blocked_vector(S, v)
    S, v, mode = _prepare(S, v, mode)
    lam = jnp.asarray(damping, dtype=S.dtype if not blocked else
                      jnp.promote_types(S.dtype, jnp.float32))
    lam = jnp.real(lam)

    W = _op_gram(S, mode=mode, precision=precision)
    sig2, U = jnp.linalg.eigh(W)                       # ascending eigenvalues
    sig2 = jnp.maximum(sig2, eps)
    # Vᵀ v = Σ⁻¹ Uᵀ S v  — computed right-to-left, never forming V (n×m… m×n).
    u = _op_matvec(S, v, precision=precision)          # (n,) or (n,k)
    Utu = _ct(U, mode) @ u
    Vt_v = Utu / _bcast(jnp.sqrt(sig2), Utu)
    core = Vt_v / _bcast(sig2 + lam, Vt_v)

    def back(y):
        return _op_rmatvec(S, U @ (y / _bcast(jnp.sqrt(sig2), y)),
                           mode=mode, precision=precision)

    if blocked:
        x = jax.tree.map(lambda vb, c, r: c + (vb - r) / lam,
                         tuple(v), tuple(back(core)), tuple(back(Vt_v)))
        return BlockedScores.concat(x) if was_flat else x
    return back(core) + (v - back(Vt_v)) / lam


def _bcast(d: jax.Array, like: jax.Array) -> jax.Array:
    """Broadcast an (n,) vector against (n,) or (n, k) operands."""
    return d if like.ndim == 1 else d[:, None]


def svd_solve(S, v, damping, *,
              mode: Mode = "auto",
              precision=_HI):
    """Appendix C "svda": direct thin SVD of S (Eq. 5).

    The CUDA ``gesvda`` kernel has no TPU analogue; XLA's SVD is used. This
    is the slowest method in the paper's Table 1 and serves as the
    correctness-anchor baseline. A blocked operator is densified first —
    the SVD itself needs the full matrix; this baseline is an oracle, not
    a production path.
    """
    if is_blocked(S):
        return _via_dense(svd_solve, S, v, damping, mode=mode,
                          precision=precision)
    mode = _resolve_mode(S, mode)
    S, v, mode = _realify(S, v, mode)
    S, v = _promote(S, v)
    lam = jnp.asarray(damping, dtype=S.real.dtype)

    # S = U Σ Vᵀ, thin: U (n,n), s (n,), Vt (n,m)
    U, s, Vt = jnp.linalg.svd(S, full_matrices=False)
    Vt_v = jnp.matmul(Vt, v, precision=precision)
    core = Vt_v / _bcast(s * s + lam, Vt_v)
    V = _ct(Vt, mode)
    return jnp.matmul(V, core, precision=precision) + \
        (v - jnp.matmul(V, Vt_v, precision=precision)) / lam


def _via_dense(solver, S, v, damping, **kw):
    """Oracle fallback: densify a blocked operator, solve, re-block."""
    if isinstance(S, LazyBlockedScores):
        S = S.materialize()
    v_blocks, was_flat = as_blocked_vector(S, v)
    x = solver(S.to_dense(), BlockedScores.concat(v_blocks), damping, **kw)
    return x if was_flat else S.split(x)


# ---------------------------------------------------------------------------
# iterative + naive baselines (paper §3 discussion)
# ---------------------------------------------------------------------------

def cg_solve(S, v, damping, *,
             mode: Mode = "auto",
             tol: float = 1e-8,
             maxiter: Optional[int] = None,
             precision=_HI):
    """Matrix-free CG on (SᵀS + λI)x = v.

    O(nm) per iteration; iteration count blows up with conditioning — the
    paper's §3 argument for preferring the direct dual solve. With a
    blocked S the CG iterates are block pytrees (jax's CG is pytree-
    native), so even the Krylov vectors never materialize flat.
    """
    blocked = is_blocked(S)
    was_flat = True
    if blocked:
        if isinstance(S, LazyBlockedScores):
            S = S.materialize()
        v, was_flat = as_blocked_vector(S, v)
    S, v, mode = _prepare(S, v, mode)
    lam = jnp.asarray(damping, dtype=S.real.dtype if not blocked
                      else jnp.promote_types(S.dtype, jnp.float32))
    lam = jnp.real(lam)

    def matvec(p):
        Sp = _op_matvec(S, p, precision=precision)
        y = _op_rmatvec(S, Sp, mode=mode, precision=precision)
        if blocked:
            return jax.tree.map(lambda yb, pb: yb + lam * pb,
                                tuple(y), tuple(p))
        return y + lam * p

    x, _ = jax.scipy.sparse.linalg.cg(matvec, v, tol=tol, maxiter=maxiter)
    if blocked and was_flat:
        return BlockedScores.concat(x)
    return x


def direct_solve(S, v, damping, *,
                 mode: Mode = "auto",
                 precision=_HI):
    """Naive O(m³): form the m×m damped Fisher and solve. Oracle for tests.
    Blocked operators are densified (this baseline materializes m×m anyway).
    """
    if is_blocked(S):
        return _via_dense(direct_solve, S, v, damping, mode=mode,
                          precision=precision)
    mode = _resolve_mode(S, mode)
    S, v, mode = _realify(S, v, mode)
    S, v = _promote(S, v)
    lam = jnp.asarray(damping, dtype=S.real.dtype)
    m = S.shape[1]
    F = jnp.matmul(_ct(S, mode), S, precision=precision) \
        + lam * jnp.eye(m, dtype=S.dtype)
    return jnp.linalg.solve(F, v)


def minsr_solve(S, f, damping, *,
                mode: Mode = "auto",
                precision=_HI):
    """RVB+23 minSR:  x = Sᵀ (SSᵀ + λĨ)⁻¹ f,  valid only when v = Sᵀ f.

    Appendix B proves this equals ``chol_solve(S, Sᵀf, λ)``; the test suite
    checks that identity. Note the *restriction*: f lives in sample space, so
    regularized losses (v ∉ row-space offsets) are not expressible — the
    paper's motivating generality argument. ``f`` is an (n,) sample-space
    vector for dense and blocked S alike; with a blocked S the result is
    returned blocked.
    """
    blocked = is_blocked(S)
    if isinstance(S, LazyBlockedScores):
        S = S.materialize()
    mode = _resolve_mode(S, mode)
    if mode == "real_part" and jnp.issubdtype(S.dtype, jnp.complexfloating):
        S = S.realify() if blocked else \
            jnp.concatenate([jnp.real(S), jnp.imag(S)], axis=0)
        f = jnp.real(f) if jnp.issubdtype(f.dtype, jnp.complexfloating) else f
        mode = "real"
    tgt = jnp.promote_types(S.dtype, jnp.float32)
    S = S.astype(tgt)
    f = f.astype(jnp.promote_types(f.dtype, tgt))
    lam = jnp.asarray(damping, dtype=jnp.zeros((), tgt).real.dtype)
    n = S.shape[0]
    W = _op_gram(S, mode=mode, precision=precision)
    W = W + lam * jnp.eye(n, dtype=W.dtype)
    L = jnp.linalg.cholesky(W)
    w = solve_triangular(L, f, lower=True)
    w = solve_triangular(_ct(L, mode), w, lower=False)
    return _op_rmatvec(S, w, mode=mode, precision=precision)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

SOLVERS: dict[str, Callable] = {
    "chol": chol_solve,
    "eigh": eigh_solve,
    "svd": svd_solve,
    "cg": cg_solve,
    "direct": direct_solve,
}


def get_solver(name: str) -> Callable:
    try:
        return SOLVERS[name]
    except KeyError:
        raise KeyError(f"unknown solver '{name}'; have {sorted(SOLVERS)}") from None
