"""Streaming curvature subsystem: rank-k update/downdate equivalence
(real/complex × dense/blocked), window algebra, streaming Gram
accumulation, and the cross-step cache policy (including inside NGD)."""
import hypothesis
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import strategies as st

from repro.core import (
    BlockedScores,
    CholFactorization,
    chol_factorize,
    chol_solve,
    residual,
)
from repro.curvature import (
    CurvatureCache,
    StreamingCurvature,
    StreamingGram,
    accumulate_gram,
    chol_append,
    chol_downdate,
    chol_drop_leading,
    chol_update,
    replace_factors,
)

RNG = np.random.default_rng(11)
WIDTHS = [60, 40, 50]


def _mk(n=24, m=150, complex_=False, seed=0):
    rng = np.random.default_rng(seed)
    S = rng.normal(size=(n, m))
    v = rng.normal(size=(m,))
    if complex_:
        S = S + 1j * rng.normal(size=(n, m))
        v = v + 1j * rng.normal(size=(m,))
        return jnp.asarray(S, jnp.complex64), jnp.asarray(v, jnp.complex64)
    return jnp.asarray(S, jnp.float32), jnp.asarray(v, jnp.float32)


def _chol(W):
    return np.asarray(jnp.linalg.cholesky(W))


# ---------------------------------------------------------------------------
# rank-k primitives
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("complex_", [False, True], ids=["real", "complex"])
@pytest.mark.parametrize("method", ["composed", "rotations"])
def test_update_downdate_match_refactorize(complex_, method):
    n, k, lam = 20, 4, 0.1
    S, _ = _mk(n=n, complex_=complex_)
    X, _ = _mk(n=n, m=k, complex_=complex_)
    W = S @ S.conj().T + lam * jnp.eye(n, dtype=S.dtype)
    L = jnp.linalg.cholesky(W)
    Lu = chol_update(L, X, method=method)
    np.testing.assert_allclose(np.asarray(Lu),
                               _chol(W + X @ X.conj().T),
                               rtol=1e-4, atol=1e-5)
    Ld = chol_downdate(Lu, X, method=method)
    np.testing.assert_allclose(np.asarray(Ld), np.asarray(L),
                               rtol=1e-4, atol=1e-5)
    # diagonal stays real positive (complex mode included)
    assert np.all(np.real(np.diagonal(np.asarray(Lu))) > 0)
    assert np.abs(np.imag(np.diagonal(np.asarray(Lu)))).max() < 1e-5


@hypothesis.settings(max_examples=8, deadline=None)
@hypothesis.given(n=st.integers(4, 24), k=st.integers(1, 5),
                  seed=st.integers(0, 2 ** 16),
                  complex_=st.sampled_from([False, True]),
                  method=st.sampled_from(["composed", "rotations"]))
def test_update_then_downdate_recovers_base_factor(n, k, seed, complex_,
                                                   method):
    """Property: for any well-conditioned base factor L and any rank-k
    columns P, ``chol_downdate(chol_update(L, P), P)`` is L again — the
    invariant the tenant platform leans on when it corrects the shared
    base factor by a delta and the delta later retracts (real +
    complex-Hermitian, both update methods)."""
    rng = np.random.default_rng(seed)
    S = rng.normal(size=(n, 4 * n))
    P = rng.normal(size=(n, k))
    if complex_:
        S = S + 1j * rng.normal(size=(n, 4 * n))
        P = P + 1j * rng.normal(size=(n, k))
    S = jnp.asarray(S / np.sqrt(4 * n),
                    jnp.complex64 if complex_ else jnp.float32)
    P = jnp.asarray(P, S.dtype)
    W = S @ S.conj().T + 0.5 * jnp.eye(n, dtype=S.dtype)
    L = jnp.linalg.cholesky(W)
    back = chol_downdate(chol_update(L, P, method=method), P, method=method)
    np.testing.assert_allclose(np.asarray(back), np.asarray(L),
                               rtol=2e-3, atol=2e-4)
    # and the updated factor really is chol(W + PP†)
    np.testing.assert_allclose(
        np.asarray(chol_update(L, P, method=method)),
        _chol(W + P @ P.conj().T), rtol=2e-3, atol=2e-4)


@hypothesis.settings(max_examples=8, deadline=None)
@hypothesis.given(seed=st.integers(0, 2 ** 16), n=st.integers(4, 16),
                  complex_=st.sampled_from([False, True]),
                  method=st.sampled_from(["composed", "rotations"]))
def test_downdate_margin_decays_toward_singularity(seed, n, complex_,
                                                   method):
    """Property: the breakdown margin is a usable early-warning signal.

    Downdating W = I + uu† by t·u hits singularity at t² = 1 + 1/‖u‖²;
    as t climbs toward that critical value the pre-clamp margin must
    fall monotonically from ≈1 toward 0 while staying positive — for the
    composed method it equals 1 − f² exactly at t = f·t_crit — so a
    monitor watching the gauge sees the drift long before the clamp."""
    rng = np.random.default_rng(seed)
    u = rng.normal(size=(n, 1))
    if complex_:
        u = u + 1j * rng.normal(size=(n, 1))
    dt = jnp.complex64 if complex_ else jnp.float32
    u = jnp.asarray(u, dt)
    W = jnp.eye(n, dtype=dt) + u @ u.conj().T
    L = jnp.linalg.cholesky(W)
    t_crit = float(np.sqrt(1 + 1 / float(jnp.real(u.conj().T @ u)[0, 0])))
    fracs = (0.2, 0.5, 0.8, 0.95, 0.999)
    margins = []
    for f in fracs:
        Ld, aux = chol_downdate(L, jnp.asarray(f * t_crit, dt) * u,
                                method=method, return_aux=True)
        assert not bool(aux.clamped)
        assert np.all(np.isfinite(np.asarray(Ld)))
        margins.append(float(aux.margin))
    assert all(0 < m <= 1 + 1e-6 for m in margins)
    assert all(a > b for a, b in zip(margins, margins[1:]))
    assert margins[0] > 0.9 and margins[-1] < 0.2
    if method == "composed":
        np.testing.assert_allclose(margins, [1 - f * f for f in fracs],
                                   rtol=1e-2, atol=1e-3)


@hypothesis.settings(max_examples=8, deadline=None)
@hypothesis.given(seed=st.integers(0, 2 ** 16),
                  overshoot=st.floats(1.01, 1.5),
                  complex_=st.sampled_from([False, True]),
                  method=st.sampled_from(["composed", "rotations"]))
def test_invalid_downdate_clamps_and_reports_not_nan(seed, overshoot,
                                                     complex_, method):
    """Property: past the breakdown point the aux is still a reportable
    statistic — ``clamped`` fires and the margin is ≤ 0 but never NaN,
    so the rule engine's ``lt 0`` comparison sees it even though the
    factor itself is garbage (which is exactly why the monitor, not the
    factor, is the place to look)."""
    rng = np.random.default_rng(seed)
    n = 10
    u = rng.normal(size=(n, 1))
    if complex_:
        u = u + 1j * rng.normal(size=(n, 1))
    dt = jnp.complex64 if complex_ else jnp.float32
    u = jnp.asarray(u, dt)
    W = jnp.eye(n, dtype=dt) + u @ u.conj().T
    L = jnp.linalg.cholesky(W)
    t_crit = float(np.sqrt(1 + 1 / float(jnp.real(u.conj().T @ u)[0, 0])))
    Ld, aux = chol_downdate(L, jnp.asarray(overshoot * t_crit, dt) * u,
                            method=method, return_aux=True)
    m = float(aux.margin)
    assert m == m                    # not NaN: the signal survives
    assert m <= 0                    # and says "invalid", signed
    assert bool(aux.clamped)
    assert float(aux.min_pivot) <= 0 or bool(aux.clamped)
    del Ld                           # invalid by construction: only the
    #                                  aux diagnostics are meaningful


def test_downdate_aux_healthy_matches_plain_result():
    """return_aux must not change the numbers: the aux path's L' is the
    plain downdate bit-for-bit on a healthy problem."""
    for method in ("composed", "rotations"):
        S, _ = _mk(n=12, seed=5)
        X, _ = _mk(n=12, m=3, seed=6)
        W = S @ S.T + 0.5 * jnp.eye(12, dtype=S.dtype)
        L = jnp.linalg.cholesky(chol_update(jnp.linalg.cholesky(W), X)
                                @ chol_update(jnp.linalg.cholesky(W),
                                              X).conj().T)
        Ld, aux = chol_downdate(L, X, method=method, return_aux=True)
        np.testing.assert_array_equal(
            np.asarray(Ld),
            np.asarray(chol_downdate(L, X, method=method)))
        assert float(aux.margin) > 0.1
        assert not bool(aux.clamped)


def test_rank1_vector_input():
    n, lam = 16, 0.2
    S, _ = _mk(n=n)
    x = jnp.asarray(RNG.normal(size=(n,)), jnp.float32)
    W = S @ S.T + lam * jnp.eye(n)
    L = jnp.linalg.cholesky(W)
    np.testing.assert_allclose(np.asarray(chol_update(L, x)),
                               _chol(W + jnp.outer(x, x)),
                               rtol=1e-4, atol=1e-5)


def test_append_and_drop_leading():
    n, k, lam = 20, 5, 0.3
    S, _ = _mk(n=n + k, m=200, seed=3)
    W = S @ S.T + lam * jnp.eye(n + k)
    Lf = jnp.linalg.cholesky(W)
    grown = chol_append(jnp.linalg.cholesky(W[:n, :n]), W[:n, n:], W[n:, n:])
    np.testing.assert_allclose(np.asarray(grown), np.asarray(Lf),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(chol_drop_leading(Lf, k)),
                               _chol(W[k:, k:]), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("complex_", [False, True], ids=["real", "complex"])
def test_replace_factors_sliding_sample_window(complex_):
    """k sample rows leave the window, k enter: one update + one downdate
    refreshes the factor to the from-scratch one."""
    n, m, k, lam = 24, 150, 3, 0.2
    idx = np.array([2, 9, 17])
    S, _ = _mk(n=n, m=m, complex_=complex_, seed=5)
    S2 = np.array(S)
    S2[idx] = np.asarray(_mk(n=k, m=m, complex_=complex_, seed=6)[0])
    S2 = jnp.asarray(S2)
    eye = jnp.eye(n, dtype=S.dtype)
    W = S @ S.conj().T + lam * eye
    W2 = S2 @ S2.conj().T + lam * eye
    L = jnp.linalg.cholesky(W)
    new_cols = (S2 @ S2[idx].conj().T) + lam * eye[:, idx]
    X, Y, Wp = replace_factors(W, new_cols, idx)
    np.testing.assert_allclose(np.asarray(Wp), np.asarray(W2),
                               rtol=1e-5, atol=1e-5)
    L2 = chol_downdate(chol_update(L, X), Y)
    np.testing.assert_allclose(np.asarray(L2), _chol(W2),
                               rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# acceptance: k update + k downdate steps on CholFactorization reproduce
# the from-scratch chol_factorize factor (real/complex × dense/blocked)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("complex_", [False, True], ids=["real", "complex"])
@pytest.mark.parametrize("blocked", [False, True], ids=["dense", "blocked"])
def test_factorization_update_downdate_roundtrip(complex_, blocked):
    n, m, k, lam = 24, 150, 4, 0.15
    mode = "complex" if complex_ else "real"
    S, v = _mk(n=n, m=m, complex_=complex_)
    X, _ = _mk(n=n, m=k, complex_=complex_, seed=9)
    Sop = BlockedScores.from_dense(S, WIDTHS) if blocked else S
    fac = chol_factorize(Sop, lam, mode=mode)

    # k rank-1 update steps == from-scratch factorization of [S X]
    up = fac
    for j in range(k):
        up = up.update(X[:, j])
    S_aug = jnp.concatenate([S, X], axis=1)
    ref = chol_factorize(S_aug, lam, mode=mode)
    np.testing.assert_allclose(np.asarray(up.L), np.asarray(ref.L),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(up.W), np.asarray(ref.W),
                               rtol=1e-4, atol=1e-5)
    # the grown factorization solves the grown system exactly
    v_aug = jnp.concatenate([v, jnp.zeros((k,), v.dtype)])
    if blocked:
        # each update appended one single-column block to the operator
        v_in = tuple(list(BlockedScores.from_dense(S, WIDTHS).split(v))
                     + [jnp.zeros((1,), v.dtype)] * k)
    else:
        v_in = v_aug
    x_up = up.solve(v_in)
    x_ref = chol_solve(S_aug, v_aug, lam, mode=mode)
    flat = x_up if not blocked else jnp.concatenate(
        [b.reshape(-1) for b in x_up])
    np.testing.assert_allclose(np.asarray(flat), np.asarray(x_ref),
                               rtol=5e-3, atol=5e-3)

    # k rank-1 downdate steps return to the original factor
    down = up
    for j in range(k):
        down = down.downdate(X[:, j], S_new=Sop)
    np.testing.assert_allclose(np.asarray(down.L), np.asarray(fac.L),
                               rtol=1e-4, atol=1e-5)
    x0 = down.solve(v)
    x0 = x0 if not blocked else jnp.concatenate(
        [b.reshape(-1) for b in x0])
    np.testing.assert_allclose(np.asarray(x0),
                               np.asarray(chol_solve(S, v, lam, mode=mode)),
                               rtol=5e-3, atol=5e-3)


def test_chol_factorize_precomputed_gram():
    S, v = _mk()
    lam = 0.2
    W = S @ S.T
    fac = chol_factorize(S, lam, W=W)
    np.testing.assert_allclose(np.asarray(fac.solve(v)),
                               np.asarray(chol_solve(S, v, lam)),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError):
        chol_factorize(S, lam, W=jnp.eye(3))


# ---------------------------------------------------------------------------
# StreamingGram
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode,complex_", [("real", False),
                                           ("complex", True),
                                           ("real_part", True)])
def test_streaming_gram_matches_full(mode, complex_):
    n, m = 16, 120
    S, v = _mk(n=n, m=m, complex_=complex_)
    op = BlockedScores.from_dense(S, [50, 30, 40])
    dual_n = 2 * n if mode == "real_part" else n
    sg = StreamingGram(dual_n, mode=mode)
    for b in op.blocks:                   # fold one piece at a time
        sg = sg.update(b)
    assert sg.m == m
    ref = chol_factorize(S, 0.1, mode=mode)
    np.testing.assert_allclose(np.asarray(sg.gram()), np.asarray(ref.W),
                               rtol=1e-5, atol=1e-5)
    # factorize with the accumulated W == the from-scratch solve
    fac = sg.factorize(S, 0.1, mode=mode)
    np.testing.assert_allclose(np.asarray(fac.solve(v)),
                               np.asarray(chol_solve(S, v, 0.1, mode=mode)),
                               rtol=5e-3, atol=5e-3)


def test_streaming_gram_update_downdate_and_pieces():
    n = 12
    S, _ = _mk(n=n, m=90, seed=2)
    op = BlockedScores.from_dense(S, [40, 50])
    # dense piece, blocked piece, and one-shot accumulate all agree
    sg = StreamingGram(n).update(op)
    np.testing.assert_allclose(np.asarray(sg.gram()),
                               np.asarray(S @ S.T), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(accumulate_gram(op.blocks)),
        np.asarray(S @ S.T), rtol=1e-5, atol=1e-5)
    # retiring a block restores the remainder
    sg2 = sg.downdate(op.blocks[1])
    np.testing.assert_allclose(np.asarray(sg2.gram()),
                               np.asarray(op.blocks[0] @ op.blocks[0].T),
                               rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError):
        StreamingGram(n + 1).update(op.blocks[0])


# ---------------------------------------------------------------------------
# CurvatureCache / StreamingCurvature policy
# ---------------------------------------------------------------------------

def test_cache_exact_on_refresh_steps_and_stats():
    n, m, lam = 16, 200, 0.1
    S, v = _mk(n=n, m=m, seed=4)
    cache = CurvatureCache(StreamingCurvature(n, refresh_every=2))
    x = cache.solve(S, v, lam)                      # first: forced refresh
    np.testing.assert_allclose(np.asarray(x),
                               np.asarray(chol_solve(S, v, lam)),
                               rtol=1e-5, atol=1e-5)
    assert int(cache.stats.refreshes) == 1 and int(cache.stats.hits) == 0
    x2 = cache.solve(S, v, lam)                     # hit: same S → same x
    np.testing.assert_allclose(np.asarray(x2), np.asarray(x),
                               rtol=1e-6, atol=1e-6)
    assert int(cache.stats.hits) == 1
    cache.solve(S, v, lam)                          # age 2 → refresh again
    assert int(cache.stats.refreshes) == 2


def test_cache_with_damping_reuse_across_lambda():
    """λ changes between steps must NOT trigger a Gram refresh — the cached
    W is re-damped per step (the with_damping identity)."""
    n, m = 16, 200
    S, v = _mk(n=n, m=m, seed=8)
    cache = CurvatureCache(StreamingCurvature(n, refresh_every=100))
    cache.solve(S, v, 0.1)
    for lam in (0.3, 0.05, 1.7):
        x = cache.solve(S, v, lam)
        np.testing.assert_allclose(np.asarray(x),
                                   np.asarray(chol_solve(S, v, lam)),
                                   rtol=1e-4, atol=1e-4)
    assert int(cache.stats.refreshes) == 1          # still only the first
    assert int(cache.stats.hits) == 3


def test_cache_drift_triggers_refresh():
    n, m, lam = 16, 200, 0.1
    S, v = _mk(n=n, m=m, seed=4)
    cache = CurvatureCache(StreamingCurvature(n, refresh_every=1000,
                                              drift_tol=0.5))
    cache.solve(S, v, lam)
    S2, _ = _mk(n=n, m=m, seed=99)                  # unrelated curvature
    x = cache.solve(S2, v, lam)
    assert int(cache.stats.refreshes) == 2          # drift fired
    np.testing.assert_allclose(np.asarray(x),
                               np.asarray(chol_solve(S2, v, lam)),
                               rtol=1e-4, atol=1e-4)
    assert float(cache.stats.last_residual) > 0.5


def test_cache_drift_frac_autotunes_from_damping_state():
    """drift_frac derives the threshold from the trust-region ratio: a
    poor ratio tightens it (refresh fires), a good ratio relaxes it (hit
    survives); a static drift_tol overrides the autotune."""
    from repro.core import DampingState

    n, m, lam = 16, 400, 0.5
    S, v = _mk(n=n, m=m, seed=4)
    S = S / jnp.sqrt(jnp.asarray(m, jnp.float32))   # ‖W‖ ~ O(1) vs λ
    # consecutive-batch-overlap perturbation: residual lands between the
    # autotune's floor (1e-3, the tight/bad-ratio tol) and a relaxed 0.9
    S2 = S + (0.1 / np.sqrt(m)) * jnp.asarray(
        np.random.default_rng(1).normal(size=(n, m)), jnp.float32)
    good = DampingState(jnp.float32(lam), jnp.float32(1.0))   # tol = 0.9
    bad = DampingState(jnp.float32(lam), jnp.float32(1e-3))   # tol = floor

    cache = CurvatureCache(StreamingCurvature(n, refresh_every=1000,
                                              drift_frac=0.9))
    cache.solve(S, v, lam, damping_state=good)
    cache.solve(S2, v, lam, damping_state=good)     # residual < 0.9 → hit
    assert int(cache.stats.hits) == 1
    cache.reset()
    cache.solve(S, v, lam, damping_state=bad)
    x = cache.solve(S2, v, lam, damping_state=bad)  # tight tol → refresh
    assert int(cache.stats.refreshes) == 2
    np.testing.assert_allclose(np.asarray(x),
                               np.asarray(chol_solve(S2, v, lam)),
                               rtol=1e-4, atol=1e-4)

    static = CurvatureCache(StreamingCurvature(n, refresh_every=1000,
                                               drift_tol=10.0,
                                               drift_frac=1e-6))
    static.solve(S, v, lam, damping_state=bad)
    static.solve(S2, v, lam, damping_state=bad)     # static 10.0 wins → hit
    assert int(static.stats.hits) == 1


def test_cache_stale_hit_is_bounded_approximation():
    """Between refreshes the solve uses a stale W with the *current* S —
    the residual quantifies the drift and must stay finite/small for
    overlapping batches."""
    n, m, lam = 16, 400, 0.5
    S, v = _mk(n=n, m=m, seed=4)
    S = S / jnp.sqrt(jnp.asarray(m, jnp.float32))   # ‖W‖ ~ O(1) vs λ
    cache = CurvatureCache(StreamingCurvature(n, refresh_every=1000))
    cache.solve(S, v, lam)
    # small perturbation ~ consecutive-batch curvature overlap
    S2 = S + (0.01 / np.sqrt(m)) * jnp.asarray(
        np.random.default_rng(1).normal(size=(n, m)), jnp.float32)
    x = cache.solve(S2, v, lam)
    assert int(cache.stats.hits) == 1
    r = float(residual(S2, v, x, lam))
    assert r < 0.05                                  # stale but close


def test_cache_blocked_and_jitted():
    n, m, lam = 16, 150, 0.2
    S, v = _mk(n=n, m=m, seed=12)
    op = BlockedScores.from_dense(S, WIDTHS)
    pol = StreamingCurvature(n, refresh_every=3)
    step = jax.jit(lambda S, v, st: pol.solve(S, v, lam, st))
    st = pol.init()
    x, st = step(op, v, st)
    np.testing.assert_allclose(np.asarray(x),
                               np.asarray(chol_solve(S, v, lam)),
                               rtol=5e-3, atol=5e-3)
    x, st = step(op, op.split(v), st)               # blocked RHS round-trip
    assert isinstance(x, tuple) and len(x) == len(WIDTHS)
    assert int(st.stats.hits) == 1


# ---------------------------------------------------------------------------
# NGD wiring
# ---------------------------------------------------------------------------

def _toy_problem(seed=0):
    rng = np.random.default_rng(seed)
    params = {"w": jnp.asarray(rng.normal(size=(6, 4)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(5,)), jnp.float32)}
    grads = jax.tree.map(lambda p: 0.1 * p, params)
    S = jnp.asarray(rng.normal(size=(8, 29)) / 3.0, jnp.float32)
    return params, grads, S


def test_ngd_curvature_exact_default_is_noop():
    from repro.optim import NaturalGradient
    params, grads, S = _toy_problem()
    upd_ref, st_ref = None, None
    for curvature in (None, "exact"):
        opt = NaturalGradient(0.1, damping=0.3, curvature=curvature)
        st = opt.init(params)
        assert st.curvature is None
        upd, st = opt.update(grads, st, params, scores=S)
        if upd_ref is None:
            upd_ref, st_ref = upd, st
        else:
            jax.tree.map(lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)), upd, upd_ref)


def test_ngd_streaming_refresh_every_step_matches_exact():
    from repro.optim import NaturalGradient
    params, grads, S = _toy_problem()
    exact = NaturalGradient(0.1, damping=0.3, momentum=0.5)
    stream = NaturalGradient(0.1, damping=0.3, momentum=0.5,
                             curvature=StreamingCurvature(8, refresh_every=1))
    se, ss = exact.init(params), stream.init(params)
    for i in range(3):
        ue, se = exact.update(grads, se, params, scores=S)
        us, ss = stream.update(grads, ss, params, scores=S)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6), ue, us)
    assert int(ss.curvature.stats.refreshes) == 3
    assert ss.curvature.W.shape == (8, 8)


def test_ngd_streaming_hits_between_refreshes():
    from repro.optim import NaturalGradient
    params, grads, S = _toy_problem()
    opt = NaturalGradient(0.1, damping=0.3,
                          curvature=StreamingCurvature(8, refresh_every=4))
    st = opt.init(params)
    for _ in range(4):
        _, st = opt.update(grads, st, params, scores=S)
    assert int(st.curvature.stats.refreshes) == 1
    assert int(st.curvature.stats.hits) == 3


def test_ngd_curvature_rejects_garbage():
    from repro.optim import NaturalGradient
    with pytest.raises(ValueError):
        NaturalGradient(0.1, curvature="approximately")


def test_streaming_curvature_mode_guards():
    with pytest.raises(ValueError):
        StreamingCurvature(8, mode="real_part")
    S, v = _mk(n=8, m=40, complex_=True)
    pol = StreamingCurvature(8)                     # real policy
    with pytest.raises(ValueError):
        pol.solve(S, v, 0.1, pol.init())
    # the complex policy handles the same inputs
    pol_c = StreamingCurvature(8, mode="complex")
    x, _ = pol_c.solve(S, v, 0.1, pol_c.init())
    np.testing.assert_allclose(
        np.asarray(x), np.asarray(chol_solve(S, v, 0.1, mode="complex")),
        rtol=1e-4, atol=1e-4)
