"""``TenantDelta`` — one tenant's curvature as a rank-r dual-space delta.

The multi-tenant memory model: every tenant shares ONE resident base
``ServeState`` (window S, Gram W, factor L — maintained exactly as
today), and owns only r dual-space delta columns. A tenant's effective
curvature is the shared window *reweighted* in dual space,

    F_t = λ·I + Sᵀ·(Ĩ + P·diag(s)·P†)·S,        P : (n, r), s ∈ {±1, 0},

i.e. the private window ``[S; P†S]`` (the base samples plus r projected
tenant samples) without ever materializing its O(n·m) rows. The solve
stays the paper's dual identity with a rank-r corrected factor: writing
M = Ĩ + P·diag(s)·P†, the Woodbury push-through gives

    F_t⁻¹ v = (v − Sᵀ·w)/λ,     (W + λ·M⁻¹)·w = S·v,

and  W + λM⁻¹ = (W + λĨ) − λ·P·(diag(s)⁻¹ + P†P)⁻¹·P†  — the base damped
Gram minus a rank-r Hermitian form. ``signed_split`` of its r×r core
turns the tenant factor into one ``chol_update`` + one ``chol_downdate``
of the *base* L at O(n²·r) (``delta_factor``), or equivalently one
``CholFactorization.update``/``.downdate`` pair (``tenant_factorization``).
Both S passes of the solve touch only the shared window — a tenant
microbatch runs the same fused serve kernel as a base microbatch with
L_t swapped in — so the resident per-tenant cost is exactly the delta:
O(n·r) bytes, independent of m. (Note the dual inversion: a tenant that
*adds* curvature, s = +1, *downdates* the dual factor — λM⁻¹ ⪯ λĨ.)

A tenant fold projects the tenant's score rows onto the shared window's
row space through the resident factor (``project_rows``: one O(n·m·k)
S pass + triangular solves — the ridge projection q = (W+λ₀Ĩ)⁻¹·S·g†,
so the folded sample is P†S's best representation of g) and FIFO-writes
the resulting dual columns into the fixed rank budget (``delta_fold``),
retiring the tenant's oldest delta columns exactly like the base window
retires samples. Folds are pure, fixed-shape functions of the stored
columns — replaying the same projected columns reproduces the delta (and
therefore the factor) bit for bit, which is what the manager's
spill/activate path (``repro.tenants.manager``) relies on.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from repro.core.operator import is_blocked
from repro.core.solvers import CholFactorization
from repro.kernels.ops import cholupdate
from repro.serve.state import ServeState, as_factorization, serve_mode

__all__ = ["TenantDelta", "init_tenant_delta", "project_rows", "delta_fold",
           "delta_correction", "delta_factor", "tenant_factorization",
           "augmented_window", "delta_nbytes"]

_HI = jax.lax.Precision.HIGHEST
_EMPTY = 1e30          # core eigenvalue sentinel for unfilled budget slots


class TenantDelta(NamedTuple):
    """One tenant's resident state (a pytree; checkpoints like any other).

    ``cols``: the (n, r) dual-space delta columns P — zero where the rank
    budget slot is unfilled. ``signs``: (r,) in {+1, −1, 0}: +1 adds the
    projected sample's curvature, −1 subtracts it (down-weighting shared
    behaviour), 0 marks an empty slot. ``cursor``: next FIFO slot in the
    rank budget. ``age``: folds applied since creation.
    """
    cols: jax.Array
    signs: jax.Array
    cursor: jax.Array
    age: jax.Array

    @property
    def rank(self) -> int:
        return self.cols.shape[1]

    @property
    def filled(self) -> jax.Array:
        return jnp.sum((self.signs != 0).astype(jnp.int32))


def init_tenant_delta(n: int, rank: int, *, dtype=jnp.float32) -> TenantDelta:
    """An empty delta: the tenant solves exactly like the base until its
    first fold. ``rank`` is the tenant's whole memory budget — r ≪ m."""
    if rank < 1:
        raise ValueError("tenant rank budget must be >= 1")
    return TenantDelta(cols=jnp.zeros((n, rank), dtype),
                       signs=jnp.zeros((rank,), jnp.float32),
                       cursor=jnp.zeros((), jnp.int32),
                       age=jnp.zeros((), jnp.int32))


def _sv_pass(S, rows, *, mode: str) -> jax.Array:
    """u = S·rows† (n, k): the one m-sized pass of a tenant fold."""
    row_blocks = tuple(rows) if isinstance(rows, (tuple, list)) else (rows,)
    S_blocks = S.blocks if is_blocked(S) else (S,)
    acc = jnp.promote_types(S_blocks[0].dtype, jnp.float32)

    def one(b, r):
        r = jnp.asarray(r)
        if r.ndim == 1:
            r = r[None, :]
        rt = r.conj().T if mode == "complex" else r.T
        return jnp.matmul(b.astype(acc), rt.astype(acc), precision=_HI)

    return sum(one(b, r) for b, r in zip(S_blocks, row_blocks))


def project_rows(state: ServeState, rows, *, jitter: float = 0.0
                 ) -> jax.Array:
    """Project tenant score rows (k, m) — dense or per-block pieces — into
    dual space through the resident base factor:

        Q = (W + λ₀Ĩ)⁻¹ · S·rows†  =  L⁻†·L⁻¹·(S·rows†)        (n, k)

    The ridge projection of each row onto the shared window's row space:
    folding Q gives the tenant the curvature of the projected samples
    Q†S, the closest window-representable stand-in for its raw rows. The
    columns are what the tenant journals — replay needs no S pass."""
    del jitter  # the resident L already carries the server's jitter
    mode = serve_mode(state)
    u = _sv_pass(state.S, rows, mode=mode)
    L = state.L.astype(jnp.promote_types(state.L.dtype, u.dtype))
    q = solve_triangular(L, u.astype(L.dtype), lower=True)
    ct = L.conj().T if mode == "complex" else L.T
    return solve_triangular(ct, q, lower=False)


def delta_fold(delta: TenantDelta, Q, *, signs=None
               ) -> Tuple[TenantDelta, Tuple[int, ...]]:
    """FIFO-write k projected columns into the rank budget; returns
    (delta', slots) with ``slots`` the budget positions written — the
    tenant-journal analogue of the window's fold slots. Pure and fixed-
    shape: replaying the same columns reproduces the delta bit for bit."""
    Q = jnp.asarray(Q)
    if Q.ndim == 1:
        Q = Q[:, None]
    n, k = Q.shape
    r = delta.rank
    if k > r:
        raise ValueError(f"cannot fold {k} columns into a rank-{r} budget")
    if Q.shape[0] != delta.cols.shape[0]:
        raise ValueError(f"delta columns have {delta.cols.shape[0]} rows, "
                         f"fold has {Q.shape[0]}")
    s = jnp.ones((k,), jnp.float32) if signs is None \
        else jnp.asarray(signs, jnp.float32).reshape(k)
    cursor = int(delta.cursor)
    slots = tuple((cursor + i) % r for i in range(k))
    idx = jnp.asarray(slots, jnp.int32)
    cols = delta.cols.at[:, idx].set(Q.astype(delta.cols.dtype))
    return delta._replace(cols=cols,
                          signs=delta.signs.at[idx].set(s),
                          cursor=jnp.asarray((cursor + k) % r, jnp.int32),
                          age=delta.age + 1), slots


def delta_correction(delta: TenantDelta, lam, *, return_cond: bool = False):
    """The signed factor correction at damping ``lam``: (up, down) with

        (W + λĨ) + up·up† − down·down†  =  W + λ·M⁻¹,

    i.e. ``L_t = chol_downdate(chol_update(L, up), down)``. Derived from
    the r×r core  diag(s)⁻¹ + P†P  (empty slots pinned at a huge positive
    eigenvalue, so their columns scale to exactly zero). All-(+1) deltas
    produce a pure downdate — adding tenant curvature shrinks λM⁻¹.

    ``return_cond=True`` appends the conditioning of the *live* core
    spectrum (max |ev| / min |ev| over genuine delta directions, 1.0 for
    an empty delta) — the eigenvalues are computed here anyway, so the
    health gauge is free."""
    P = delta.cols
    r = delta.rank
    s = delta.signs.astype(P.real.dtype)
    # diag(s)^-1 with empty slots at _EMPTY: their eigenpairs decouple
    # (P column is zero there) and the 1/sqrt scale flushes to ~0
    d_inv = jnp.where(s == 0, _EMPTY, jnp.where(s < 0, -1.0, 1.0))
    core = jnp.diag(d_inv).astype(P.dtype) + jnp.matmul(
        P.conj().T, P, precision=_HI)
    core = (core + core.conj().T) / 2
    ev, V = jnp.linalg.eigh(core)
    lam = jnp.real(jnp.asarray(lam, P.real.dtype))
    live = jnp.abs(ev) < (_EMPTY / 1e6)          # genuine delta directions
    scale = jnp.where(live,
                      jnp.sqrt(lam / jnp.maximum(jnp.abs(ev), 1e-30)), 0.0)
    C = jnp.matmul(P, V, precision=_HI) * scale[None, :]
    up = jnp.where(ev < 0, 1.0, 0.0)[None, :] * C     # chol_update columns
    down = jnp.where(ev > 0, 1.0, 0.0)[None, :] * C   # chol_downdate columns
    if return_cond:
        a = jnp.real(jnp.abs(ev))
        mx = jnp.max(jnp.where(live, a, 0.0))
        mn = jnp.min(jnp.where(live, a, jnp.inf))
        cond = jnp.where(jnp.isfinite(mn) & (mx > 0),
                         mx / jnp.maximum(mn, 1e-30), 1.0)
        return up, down, cond
    return up, down


def delta_factor(delta: TenantDelta, L: jax.Array, lam, *,
                 return_cond: bool = False):
    """The tenant's resident-λ factor from the base factor: O(n²·r).

    ``L`` must be the base chol(W + λĨ) at the same ``lam``; hot tenants
    cache the result (``TenantManager``), cold tenants recompute on
    demand. An empty delta returns a factor equal to L. Both rank-r
    sweeps go through ``repro.kernels.ops.cholupdate``: the in-VMEM
    Pallas kernel on a TPU, the ``repro.curvature.update`` reference
    elsewhere. ``return_cond=True``: also return the live core
    conditioning (see ``delta_correction``) as ``(L_t, cond)``."""
    if return_cond:
        up, down, cond = delta_correction(delta, lam, return_cond=True)
    else:
        up, down = delta_correction(delta, lam)
    L_t = cholupdate(cholupdate(L, up, sign=+1), down, sign=-1)
    return (L_t, cond) if return_cond else L_t


def tenant_factorization(state: ServeState, delta: TenantDelta, *,
                         jitter: float = 0.0, lam=None,
                         L: Optional[jax.Array] = None) -> CholFactorization:
    """The tenant's view of the shared window as a first-class solver.

    Built through ``CholFactorization.update``/``.downdate`` (S kept —
    the delta never touches the window), so every solver affordance
    (multi-RHS ``solve``, monitored residuals) applies to the tenant.
    ``lam`` re-dampens from the cached W first (the tenant mixed-λ path);
    ``L`` short-circuits the O(n²·r) correction with a cached factor."""
    fac = as_factorization(state, jitter=jitter)
    if lam is not None and float(lam) != float(state.lam0):
        fac = fac.with_damping(lam)
    if L is not None:
        return fac._replace(S=fac.S, W=fac.W, L=L)
    up, down = delta_correction(delta, fac.lam)
    return fac.update(up, S_new=fac.S).downdate(down, S_new=fac.S)


def augmented_window(state: ServeState, delta: TenantDelta):
    """The tenant's *private window* ``[S; P†S]`` — the O((n+r)·m) state
    the delta replaces. Only the from-scratch reference path (tests,
    ``benchmarks/serve_tenants.py``) ever materializes it; requires an
    all-(+1) dense delta (a down-weighting column is not a window row)."""
    if is_blocked(state.S):
        raise NotImplementedError("reference window: dense S only")
    if bool(jnp.any(delta.signs < 0)):
        raise ValueError("negative-sign delta has no window equivalent")
    P = delta.cols
    mode = serve_mode(state)
    Pt = P.conj().T if mode == "complex" else P.T
    S = state.S.astype(jnp.promote_types(state.S.dtype, P.dtype))
    extra = jnp.matmul(Pt.astype(S.dtype), S, precision=_HI)
    return jnp.concatenate([S, extra], axis=0)


def delta_nbytes(delta: TenantDelta) -> int:
    """Resident bytes of the delta — the O(n·r) the platform is for."""
    return sum(int(leaf.nbytes) for leaf in jax.tree.leaves(delta))
