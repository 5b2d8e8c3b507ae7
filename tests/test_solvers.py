"""Solver correctness: every method vs the direct O(m³) oracle, the paper's
SR variants, and property-based invariants (hypothesis)."""
import hypothesis
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import strategies as st

from repro.core import (
    SOLVERS,
    ConstantDamping,
    LevenbergMarquardtDamping,
    center_scores,
    chol_solve,
    direct_solve,
    eigh_solve,
    gram,
    gram_chunked,
    get_solver,
    minsr_solve,
    residual,
    svd_solve,
)

RNG = np.random.default_rng(0)


def make_problem(n=24, m=150, lam=0.1, dtype=jnp.float32, complex_=False,
                 seed=0):
    rng = np.random.default_rng(seed)
    S = rng.normal(size=(n, m))
    v = rng.normal(size=(m,))
    if complex_:
        S = S + 1j * rng.normal(size=(n, m))
        v = v + 1j * rng.normal(size=(m,))
        return jnp.asarray(S, jnp.complex64), jnp.asarray(v, jnp.complex64), lam
    return jnp.asarray(S, dtype), jnp.asarray(v, dtype), lam


@pytest.mark.parametrize(
    "name,n,m,lam",
    [pytest.param(nm, 24, 150, 0.1, id=nm) for nm in sorted(SOLVERS)]
    # n = 520 takes several Gram panels (5 of 128, the last ragged)
    + [pytest.param(nm, 520, 640, 10.0, id=nm + "-panels")
       for nm in ("chol", "eigh")])
def test_solver_matches_direct(name, n, m, lam):
    S, v, lam = make_problem(n=n, m=m, lam=lam)
    x_ref = direct_solve(S, v, lam)
    x = get_solver(name)(S, v, lam)
    np.testing.assert_allclose(np.asarray(x), np.asarray(x_ref),
                               rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("name", ["chol", "eigh", "svd"])
def test_batched_rhs(name):
    S, _, lam = make_problem()
    V = jnp.asarray(RNG.normal(size=(S.shape[1], 3)), jnp.float32)
    X = get_solver(name)(S, V, lam)
    for k in range(3):
        np.testing.assert_allclose(
            np.asarray(X[:, k]),
            np.asarray(get_solver(name)(S, V[:, k], lam)),
            rtol=5e-3, atol=5e-3)


def test_complex_hermitian_mode():
    # complex64 ⇒ looser tolerance: the damped system's conditioning
    # amplifies single-precision roundoff ~κ(F)×
    S, v, lam = make_problem(complex_=True, lam=0.5)
    x = chol_solve(S, v, lam)                 # mode auto → complex
    x_ref = direct_solve(S, v, lam)
    np.testing.assert_allclose(np.asarray(x), np.asarray(x_ref),
                               rtol=2e-2, atol=2e-3)


def test_real_part_mode_matches_concat():
    """Paper §3: F = Re[S†S] ⇔ S ← concat[Re S, Im S] on the sample axis."""
    S, v, lam = make_problem(complex_=True, lam=0.5)
    vr = jnp.real(v)
    x = chol_solve(S, vr, lam, mode="real_part")
    S2 = jnp.concatenate([jnp.real(S), jnp.imag(S)], axis=0)
    x_ref = direct_solve(S2, vr, lam)
    np.testing.assert_allclose(np.asarray(x), np.asarray(x_ref),
                               rtol=2e-2, atol=2e-3)


def test_minsr_equivalence_appendix_b():
    """When v = Sᵀf, minSR (RVB+23) equals Algorithm 1 (Appendix B)."""
    S, _, lam = make_problem()
    f = jnp.asarray(RNG.normal(size=(S.shape[0],)), jnp.float32)
    v = S.T @ f
    np.testing.assert_allclose(np.asarray(minsr_solve(S, f, lam)),
                               np.asarray(chol_solve(S, v, lam)),
                               rtol=5e-3, atol=5e-3)


def test_minsr_fails_off_rowspace_but_chol_does_not():
    """The generality claim: minSR requires v ∈ row-space(S); Algorithm 1
    handles arbitrary v (e.g. weight decay added to the gradient)."""
    S, v, lam = make_problem(n=8, m=64)
    x = chol_solve(S, v, lam)
    assert float(residual(S, v, x, lam)) < 1e-3


def test_centering():
    O = jnp.asarray(RNG.normal(size=(32, 64)) + 5.0, jnp.float32)
    S = center_scores(O)
    np.testing.assert_allclose(np.asarray(jnp.sum(S, axis=0)),
                               np.zeros(64), atol=1e-4)


def test_gram_chunked_matches():
    S, _, _ = make_problem(n=16, m=130)
    W = gram_chunked(S, 32)
    np.testing.assert_allclose(np.asarray(W), np.asarray(S @ S.T),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("m,chunk", [(130, 32), (127, 64), (50, 7)])
def test_gram_chunked_padding_path(m, chunk):
    """m % chunk != 0 exercises the zero-pad tail chunk — exact because
    zero columns contribute nothing to S·Sᵀ."""
    assert m % chunk != 0
    S, _, _ = make_problem(n=12, m=m)
    W = gram_chunked(S, chunk)
    np.testing.assert_allclose(np.asarray(W), np.asarray(S @ S.T),
                               rtol=1e-5, atol=1e-4)


def test_gram_chunked_complex_accumulation_dtype():
    """Complex mode: accumulator must be complex64+ (not the real promote),
    the result must match S·S† including the padded-tail case."""
    S, _, _ = make_problem(n=8, m=45, complex_=True)
    W = gram_chunked(S, 16, mode="complex")
    assert jnp.issubdtype(W.dtype, jnp.complexfloating)
    assert W.dtype == jnp.complex64
    np.testing.assert_allclose(np.asarray(W), np.asarray(S @ S.conj().T),
                               rtol=1e-5, atol=1e-4)
    # bf16-stored complex is not a thing; but fp64-promoted real input
    # must accumulate in float64 when x64 is off → stays float32
    Sr, _, _ = make_problem(n=8, m=45)
    Wr = gram_chunked(Sr.astype(jnp.bfloat16), 16)
    assert Wr.dtype == jnp.float32


def _dot_macs(jaxpr):
    """Multiply-adds of every dot_general in a jaxpr, sub-jaxprs included."""
    macs = []
    for eq in jaxpr.eqns:
        if eq.primitive.name == "dot_general":
            (contract, _), _ = eq.params["dimension_numbers"]
            k = int(np.prod([eq.invars[0].aval.shape[d] for d in contract]))
            macs.append(int(np.prod(eq.outvars[0].aval.shape)) * k)
        for p in eq.params.values():
            sub = getattr(p, "jaxpr", p)
            if hasattr(sub, "eqns"):
                macs += _dot_macs(sub)
    return macs


@pytest.mark.parametrize("complex_", [False, True], ids=["real", "complex"])
@pytest.mark.parametrize("n,m,panels", [(100, 3000, 1), (200, 3000, 2),
                                        (512, 3000, 4), (600, 3000, 5),
                                        (2048, 200, 8)],
                         ids=["plain", "small", "exact", "ragged", "table1"])
def test_gram_symmetric_panels(n, m, panels, complex_):
    """The dense Gram computes only the lower block triangle, one dot per
    block column of ⌈n/b⌉ (one full dot for n ≤ 128), and mirrors it: W
    matches the full HIGHEST product, is exactly symmetric (Hermitian),
    and at n = 2048 costs ≤ 0.6× the full product's multiply-adds."""
    mode = "complex" if complex_ else "real"
    S, _, _ = make_problem(n=n, m=m, complex_=complex_, seed=n)
    W = gram(S, mode=mode)
    Wr = jnp.matmul(S, S.conj().T if complex_ else S.T,
                    precision=jax.lax.Precision.HIGHEST)
    assert W.dtype == Wr.dtype
    scale = float(jnp.max(jnp.abs(Wr)))
    assert float(jnp.max(jnp.abs(W - Wr))) <= 5e-6 * scale
    assert bool(jnp.all(W == (W.conj().T if complex_ else W.T)))

    macs = _dot_macs(jax.make_jaxpr(lambda S: gram(S, mode=mode))(S).jaxpr)
    assert len(macs) == panels
    if panels > 1:
        assert sum(macs) < n * n * m
    if n == 2048:
        assert sum(macs) <= 0.6 * n * n * m


def test_bf16_scores_promote():
    S, v, lam = make_problem()
    x16 = chol_solve(S.astype(jnp.bfloat16), v.astype(jnp.bfloat16), lam)
    x32 = chol_solve(S, v, lam)
    assert x16.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(x16), np.asarray(x32),
                               rtol=0.1, atol=0.05)


# ---------------------------------------------------------------------------
# hypothesis property tests
# ---------------------------------------------------------------------------

@hypothesis.settings(max_examples=15, deadline=None)
@hypothesis.given(
    n=st.integers(2, 40), m=st.integers(41, 200),
    lam=st.floats(1e-3, 10.0), seed=st.integers(0, 2**16))
def test_property_residual_small(n, m, lam, seed):
    """(SᵀS + λI)x = v holds for random problems; λ floored at 1e-3 and the
    residual bound scaled with the damped system's fp32 condition number
    κ ≈ (‖S‖² + λ)/λ."""
    S, v, _ = make_problem(n=n, m=m, seed=seed)
    x = chol_solve(S, v, lam)
    kappa = (float(jnp.linalg.norm(S) ** 2) + lam) / lam
    assert float(residual(S, v, x, lam)) < max(1e-3, 3e-6 * kappa)


@hypothesis.settings(max_examples=10, deadline=None)
@hypothesis.given(
    n=st.integers(2, 24), m=st.integers(25, 120),
    lam=st.floats(1e-3, 1.0), seed=st.integers(0, 2**16))
def test_property_solvers_agree(n, m, lam, seed):
    S, v, _ = make_problem(n=n, m=m, seed=seed)
    xc = chol_solve(S, v, lam)
    xe = eigh_solve(S, v, lam)
    xs = svd_solve(S, v, lam)
    np.testing.assert_allclose(np.asarray(xc), np.asarray(xe),
                               rtol=5e-2, atol=1e-3)
    np.testing.assert_allclose(np.asarray(xc), np.asarray(xs),
                               rtol=5e-2, atol=1e-3)


@hypothesis.settings(max_examples=10, deadline=None)
@hypothesis.given(lam0=st.floats(1e-4, 1.0), rho=st.floats(-1.0, 2.0))
def test_property_lm_damping_direction(lam0, rho):
    """LM policy: λ grows iff ρ < ρ_bad, shrinks iff ρ > ρ_good."""
    pol = LevenbergMarquardtDamping(lam0)
    st0 = pol.init()
    st1 = pol.update(st0, actual_reduction=jnp.asarray(rho),
                     predicted_reduction=jnp.asarray(1.0))
    lam1 = float(st1.lam)
    if rho < pol.rho_bad:
        assert lam1 >= float(st0.lam)
    elif rho > pol.rho_good:
        assert lam1 <= float(st0.lam)
    else:
        assert lam1 == pytest.approx(float(st0.lam))


def test_constant_damping_is_constant():
    pol = ConstantDamping(0.3)
    st0 = pol.init()
    st1 = pol.update(st0, actual_reduction=0.0, predicted_reduction=1.0)
    assert float(st1.lam) == pytest.approx(0.3)


# ---------------------------------------------------------------------------
# CholFactorization reuse guarantees the curvature cache builds on
# ---------------------------------------------------------------------------

def test_with_damping_multi_lambda_reuse_matches_fresh():
    """Sweeping λ over a cached factorization must equal a fresh
    ``chol_solve`` at every λ — the reuse path the curvature cache and LM
    damping schedules lean on."""
    from repro.core import SolverStats, chol_factorize

    S, v, lam = make_problem(n=20, m=160)
    fac = chol_factorize(S, lam)
    for lam2 in (1e-3, 0.05, lam, 0.9, 7.0):
        fac2 = fac.with_damping(lam2)
        assert float(fac2.lam) == pytest.approx(lam2)
        x, stats = fac2.solve(v, return_stats=True)
        np.testing.assert_allclose(np.asarray(x),
                                   np.asarray(chol_solve(S, v, lam2)),
                                   rtol=1e-5, atol=1e-5)
        assert isinstance(stats, SolverStats)
        assert np.isfinite(float(stats.residual_norm))
        assert float(stats.residual_norm) < 1e-2
        assert float(stats.gram_cond_proxy) >= 1.0
    # the cached undamped Gram is shared, not recomputed
    assert fac.with_damping(0.5).W is fac.W


def test_factorization_multi_rhs_matches_fresh():
    from repro.core import chol_factorize

    S, _, lam = make_problem(n=16, m=120, seed=3)
    V = jnp.asarray(RNG.normal(size=(S.shape[1], 4)), jnp.float32)
    fac = chol_factorize(S, lam)
    X, stats = fac.solve(V, return_stats=True)
    assert X.shape == V.shape
    np.testing.assert_allclose(np.asarray(X),
                               np.asarray(chol_solve(S, V, lam)),
                               rtol=1e-5, atol=1e-5)
    assert np.isfinite(float(stats.residual_norm))
    # column-by-column agreement with independent solves
    for j in range(V.shape[1]):
        np.testing.assert_allclose(np.asarray(X[:, j]),
                                   np.asarray(chol_solve(S, V[:, j], lam)),
                                   rtol=1e-4, atol=1e-5)


def test_lm_damping_clamp_bounds():
    """λ must stay inside [lam_min, lam_max] no matter how long the gain
    ratio stays bad/good."""
    pol = LevenbergMarquardtDamping(1.0, grow=10.0, shrink=0.1,
                                    lam_min=1e-3, lam_max=1e2)
    st = pol.init()
    for _ in range(10):                         # ρ ≈ 0 → grow every step
        st = pol.update(st, actual_reduction=jnp.asarray(0.0),
                        predicted_reduction=jnp.asarray(1.0))
    assert float(st.lam) == pytest.approx(pol.lam_max)
    for _ in range(20):                         # ρ ≈ 1 → shrink every step
        st = pol.update(st, actual_reduction=jnp.asarray(1.0),
                        predicted_reduction=jnp.asarray(1.0))
    assert float(st.lam) == pytest.approx(pol.lam_min)
    assert float(st.last_ratio) == pytest.approx(1.0)
