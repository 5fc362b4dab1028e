"""Differential tests for the static-pivoting solver (DESIGN.md §12):
``repro.solver`` against dense numpy references on every checked-in
fixture, the static-vs-threshold factorization contrast, the
AWPM-converges / unpivoted-diverges refinement result, and the
batched-RHS bit-consistency contract."""
import pathlib

import numpy as np
import pytest

import repro.solver as solver
from repro.core import ref
from repro.core.dual import dual_certificate
from repro.core.preflight import PreflightError
from repro.data.mtx import read_mtx
from repro.data.weight_transforms import log2_scaled
from repro.solver import (CsrMatrix, awpm_pivoting, identity_pivoting,
                          lu_solve_once, refine, solve_linear_system,
                          sparse_lu)

DATA = pathlib.Path(__file__).parent / "data"
FIXTURES = sorted(p.stem for p in DATA.glob("*.mtx"))


def load(stem):
    coo = read_mtx(DATA / f"{stem}.mtx")
    val = np.asarray(coo.val)
    dtype = np.complex128 if np.iscomplexobj(val) else np.float64
    return (np.asarray(coo.row, np.int64), np.asarray(coo.col, np.int64),
            val.astype(dtype), coo.nrows)


def dense_of(row, col, val, n):
    out = np.zeros((n, n), dtype=val.dtype)
    np.add.at(out, (row, col), val)
    return out


def rhs_for(n, val, seed=11):
    rng = np.random.default_rng(seed)
    b = rng.standard_normal(n)
    if np.iscomplexobj(val):
        b = b + 1j * rng.standard_normal(n)
    return b


# --------------------------------------------------------------------------
# sparse LU: reconstruction + the static/threshold contrast
# --------------------------------------------------------------------------


@pytest.mark.parametrize("stem", FIXTURES)
def test_threshold_lu_reconstructs_fixture(stem):
    """Threshold partial pivoting must factor every fixture exactly:
    ``A[row_perm] == (I + L_strict) @ U`` to factorization round-off."""
    row, col, val, n = load(stem)
    a = CsrMatrix.from_coo(row, col, val, n)
    f = sparse_lu(a, mode="threshold")
    pa = a.to_dense()[f.row_perm]
    lu = (np.eye(n) + f.L.to_dense()) @ f.U.to_dense()
    amax = float(np.abs(val).max())
    tol = 64 * n * np.finfo(np.float64).eps * amax * \
        max(f.stats.pivot_growth, 1.0)
    assert np.max(np.abs(pa - lu)) <= tol
    assert f.stats.mode == "threshold"
    assert f.stats.nnz_l + f.stats.nnz_u == \
        pytest.approx(f.stats.fill_ratio * f.stats.nnz_in)


@pytest.mark.parametrize("stem", FIXTURES)
def test_static_lu_on_awpm_scaled_system_is_tame(stem):
    """After AWPM permutation + MC64 scalings, STATIC (no-pivoting) LU is
    numerically safe: unit diagonal in, O(1) pivot growth out, zero GESP
    perturbations — the claim the whole subsystem exists to measure."""
    row, col, val, n = load(stem)
    pivot, _ = awpm_pivoting(row, col, val, n)
    scaled = CsrMatrix.from_coo(*pivot.scaled_coo(row, col, val), n)
    f = sparse_lu(scaled, mode="static")
    assert np.array_equal(f.row_perm, np.arange(n))  # static commits
    assert f.stats.swaps == 0
    assert f.stats.perturbed_pivots == 0
    assert f.stats.pivot_growth <= 4.0
    pa = scaled.to_dense()
    lu = (np.eye(n) + f.L.to_dense()) @ f.U.to_dense()
    assert np.max(np.abs(pa - lu)) <= 64 * n * np.finfo(np.float64).eps


def test_static_vs_threshold_growth_contrast():
    """The factorization-level version of the paper's story on the
    planted ill-conditioned fixture: unpivoted static LU suffers
    astronomical pivot growth (and GESP floors most pivots), threshold
    partial pivoting keeps growth O(1) by swapping rows."""
    row, col, val, n = load("illcond9")
    a = CsrMatrix.from_coo(row, col, val, n)
    static = sparse_lu(a, mode="static")
    tpp = sparse_lu(a, mode="threshold")
    assert static.stats.pivot_growth > 1e12
    assert static.stats.perturbed_pivots > 0
    assert tpp.stats.pivot_growth <= 10.0
    assert tpp.stats.perturbed_pivots == 0
    assert tpp.stats.swaps > 0


def test_gesp_floor_on_missing_diagonal():
    """A structurally absent pivot does not abort static mode: GESP bumps
    it to the floor and counts the perturbation (refinement then decides
    whether the result is usable — here it is not, which is fine)."""
    a = CsrMatrix.from_coo([0, 1], [1, 0], [2.0, 3.0], 2)
    f = sparse_lu(a, mode="static")
    assert f.stats.perturbed_pivots >= 1
    floor = float(np.sqrt(np.finfo(np.float32).eps)) * 3.0
    assert f.stats.min_pivot == pytest.approx(floor)


def test_sparse_lu_rejects_bad_inputs():
    a = CsrMatrix.from_coo([0, 1], [0, 1], [1.0, 1.0], 2)
    with pytest.raises(ValueError, match="mode"):
        sparse_lu(a, mode="full")
    with pytest.raises(ValueError, match="threshold"):
        sparse_lu(a, mode="threshold", threshold=0.0)
    with pytest.raises(ValueError, match="structurally singular"):
        # column 1 is empty: threshold pivoting has nothing to swap in
        sparse_lu(CsrMatrix.from_coo([0, 1], [0, 0], [1.0, 1.0], 2),
                  mode="threshold")
    with pytest.raises(ValueError, match="all-zero"):
        sparse_lu(CsrMatrix.from_coo([], [], [], 2))


# --------------------------------------------------------------------------
# end-to-end: differential against dense numpy on every fixture
# --------------------------------------------------------------------------


@pytest.mark.parametrize("stem", FIXTURES)
def test_solve_matches_dense_reference(stem):
    row, col, val, n = load(stem)
    b = rhs_for(n, val)
    rep = solve_linear_system((row, col, val, n), b, pivoting="awpm")
    assert rep.ok, rep.summary()
    dense = dense_of(row, col, val, n)
    x_ref = np.linalg.solve(dense, b)
    cond = np.linalg.cond(dense)
    err = np.linalg.norm(rep.x - x_ref) / np.linalg.norm(x_ref)
    assert err <= 100 * cond * max(float(np.max(rep.residual)), 1e-16), \
        f"{stem}: rel error {err:.3e} vs cond {cond:.3e}"
    # the report's residual is the TRUE f64 residual of the returned x
    rr = np.linalg.norm(b - dense @ rep.x) / np.linalg.norm(b)
    assert float(np.max(rep.residual)) == pytest.approx(rr, rel=1e-6)


@pytest.mark.parametrize("stem", FIXTURES)
def test_awpm_scaled_diagonal_is_unit(stem):
    """Every fixture's AWPM certificate is tight, so the MC64 scaling
    identity must land the matched diagonal at exactly 1 and every scaled
    entry at most 1 — dominance by construction, not luck."""
    row, col, val, n = load(stem)
    pivot, result = awpm_pivoting(row, col, val, n)
    assert bool(np.asarray(result.perfect).all())
    assert pivot.certificate.tight
    diag = pivot.scaled_diag(row, col, val)
    assert np.allclose(diag, 1.0, atol=1e-9)
    _, _, pv = pivot.scaled_coo(row, col, val)
    assert float(np.abs(pv).max()) <= 1.0 + 1e-9
    # the permutation round-trips: original row i sits at row_position[i]
    assert np.array_equal(pivot.row_perm[pivot.row_position],
                          np.arange(n))


def test_contrast_awpm_converges_unpivoted_diverges():
    """The headline result on the planted ill-conditioned fixture: the
    identical factorization+refinement pipeline converges with AWPM static
    pivoting and fails without it."""
    row, col, val, n = load("illcond9")
    b = rhs_for(n, val)
    good = solve_linear_system((row, col, val, n), b, pivoting="awpm")
    bad = solve_linear_system((row, col, val, n), b, pivoting="none")
    assert good.ok
    assert float(np.max(good.residual)) <= 1e-10
    assert good.lu_stats.pivot_growth <= 4.0
    assert not bad.ok
    assert bad.lu_stats.pivot_growth > 1e12
    assert float(np.max(bad.residual)) > 1e-6
    assert bool((bad.refinement.diverged | bad.refinement.stalled).all())
    # threshold partial pivoting also rescues it — matching replaces
    # exactly the work the classical solver spends at factor time
    tpp = solve_linear_system((row, col, val, n), b, pivoting="none",
                              lu_mode="threshold")
    assert tpp.ok


@pytest.mark.skipif(not ref.HAVE_SCIPY, reason="needs scipy oracle")
def test_reference_arm_matches_awpm_on_fixtures():
    """AWPM vs the exact Hungarian matching, identical scaling recovery:
    on the fixtures both arms converge with unit scaled diagonals."""
    for stem in ("circuit8", "illcond9"):
        row, col, val, n = load(stem)
        b = rhs_for(n, val)
        rep = solve_linear_system((row, col, val, n), b,
                                  pivoting="reference")
        assert rep.ok, f"{stem}: {rep.summary()}"
        assert rep.matching_tight
        assert rep.scaled_diag_min == pytest.approx(1.0, abs=1e-9)


def test_input_forms_agree_bitwise():
    """Dense array, CsrMatrix, and COO tuple are the same system — the
    returned x must be bit-identical across input forms."""
    row, col, val, n = load("circuit8")
    b = rhs_for(n, val)
    from_coo = solve_linear_system((row, col, val, n), b)
    from_dense = solve_linear_system(dense_of(row, col, val, n), b)
    from_csr = solve_linear_system(CsrMatrix.from_coo(row, col, val, n), b)
    assert np.array_equal(from_coo.x, from_dense.x)
    assert np.array_equal(from_coo.x, from_csr.x)


def test_complex_fixture_solves():
    row, col, val, n = load("zcoil7")
    assert np.iscomplexobj(val)
    b = rhs_for(n, val)
    rep = solve_linear_system((row, col, val, n), b)
    assert rep.ok
    assert np.iscomplexobj(rep.x)
    x_ref = np.linalg.solve(dense_of(row, col, val, n), b)
    assert np.linalg.norm(rep.x - x_ref) <= 1e-8 * np.linalg.norm(x_ref)


# --------------------------------------------------------------------------
# batching: the bit-consistency contract
# --------------------------------------------------------------------------


@pytest.mark.parametrize("batch", [2, 4, 8])
def test_batched_rhs_bit_identical_to_single(batch):
    """Solving [B, n] right-hand sides must reproduce each single-RHS
    solve bit-for-bit, lane by lane — the triangular sweeps and the
    residual path are written to be shape-invariant (DESIGN.md §12)."""
    row, col, val, n = load("circuit8")
    rng = np.random.default_rng(23)
    bs = rng.standard_normal((batch, n))
    rep_b = solve_linear_system((row, col, val, n), bs)
    assert rep_b.x.shape == (batch, n)
    assert rep_b.ok
    for lane in range(batch):
        rep_1 = solve_linear_system((row, col, val, n), bs[lane])
        assert np.array_equal(rep_b.x[lane], rep_1.x), f"lane {lane}"
        assert rep_b.residual[lane] == rep_1.residual[0]


def test_refine_freezes_lanes_independently():
    """One diverging lane must not poison its batch: refine illcond9's
    garbage static factors with a batch, and every lane freezes with its
    own flag while the array stays rectangular."""
    row, col, val, n = load("illcond9")
    a = CsrMatrix.from_coo(row, col, val, n)
    f = sparse_lu(a, mode="static")
    rng = np.random.default_rng(5)
    b = rng.standard_normal((3, n))
    out = refine(a, f, b, tol=1e-12, max_iter=10)
    assert out.residuals.shape[1] == 3
    assert out.x.shape == (3, n)
    assert not out.converged.any()
    assert bool((out.diverged | out.stalled).all())
    # frozen lanes repeat their freeze-time residual on the record
    assert np.array_equal(out.residuals[-1], out.final_residual)


def test_lu_solve_once_single_is_b1_lift():
    row, col, val, n = load("bands6_sym")
    pivot, _ = awpm_pivoting(row, col, val, n)
    scaled = CsrMatrix.from_coo(*pivot.scaled_coo(row, col, val), n)
    f = sparse_lu(scaled, mode="static")
    b = rhs_for(n, val)
    x1 = lu_solve_once(f, b)
    xb = lu_solve_once(f, np.stack([b, 2.0 * b]))
    assert x1.shape == (n,) and xb.shape == (2, n)
    assert np.array_equal(x1, xb[0])
    # a single f32 pass already lands near the f32 noise floor
    rel = np.linalg.norm(b - scaled.to_dense() @ x1) / np.linalg.norm(b)
    assert rel < 1e-4


# --------------------------------------------------------------------------
# structural failure modes + dual potentials accessor
# --------------------------------------------------------------------------


def test_structural_singularity_raises_preflight():
    # column 2 has no entries: no perfect matching, no pivot order
    row = np.array([0, 1, 2])
    col = np.array([0, 1, 0])
    val = np.array([1.0, 2.0, 3.0])
    with pytest.raises(PreflightError):
        solve_linear_system((row, col, val, 3), np.ones(3))
    # check=False only lets the UNMATCHED arm proceed past preflight
    with pytest.raises(PreflightError):
        solve_linear_system((row, col, val, 3), np.ones(3),
                            pivoting="awpm", check=False)


def test_solve_rejects_bad_arguments():
    row, col, val, n = load("bands6_sym")
    with pytest.raises(ValueError, match="pivoting"):
        solve_linear_system((row, col, val, n), np.ones(n),
                            pivoting="partial")
    with pytest.raises(ValueError, match="width"):
        solve_linear_system((row, col, val, n), np.ones(n + 1))
    with pytest.raises(ValueError, match="square"):
        solve_linear_system(np.ones((2, 3)), np.ones(3))


def test_potentials_accessor_is_feasible_and_copied():
    """``DualCertificate.potentials()`` — the hook the MC64 scaling
    recovery consumes: feasible on every edge, tight on matched edges,
    and returning copies the caller can freely mutate."""
    row, col, val, n = load("circuit8")
    a = np.abs(val)
    w = log2_scaled(row, col, a, n)
    _, result = awpm_pivoting(row, col, val, n)
    mate = np.asarray(result.mate_row)[:n]
    cert = dual_certificate(row, col, w, n, mate)
    u, v = cert.potentials()
    assert u.dtype == v.dtype == np.float64
    slack = u[row] + v[col] - w
    assert float(slack.min()) >= -1e-9  # feasible everywhere
    matched = mate[col] == row
    assert cert.tight
    assert float(np.abs(slack[matched]).max()) <= 1e-9
    u[:] = -1e9  # mutating the return must not corrupt the certificate
    u2, _ = cert.potentials()
    assert float(u2.min()) > -1e9


def test_identity_pivoting_is_noop():
    p = identity_pivoting(4)
    b = np.arange(4.0)
    assert np.array_equal(p.scale_rhs(b), b)
    assert np.array_equal(p.unscale_solution(b), b)
    with pytest.raises(ValueError, match="permutation"):
        solver.ScaledPivoting(n=2, row_perm=np.array([0, 0]),
                              dr=np.ones(2), dc=np.ones(2))


# --------------------------------------------------------------------------
# property test (hypothesis optional) + export surface
# --------------------------------------------------------------------------


def _dominant_system(n, seed, offsets, cyclic):
    """Row-dominant system with rows scaled over 2^-20..2^20: diagonal d_i,
    off-diagonals at columns ``i + k`` for k in ``offsets`` (mod n when
    ``cyclic``, else dropped outside 0..n-1), each at most 0.22 d_i. The
    right-hand side is ``A @ x_true``, so every entry of b carries its
    row's scale: with a b drawn independently of the rows, no float64 x
    can meet a 1e-10 relative residual (the floor,
    eps * ||A|| ||x|| / ||b||, exceeds it)."""
    rng = np.random.default_rng(seed)
    row, col, val = [], [], []
    for i in range(n):
        d = float(np.exp2(rng.integers(-20, 20))) * (1.0 + rng.random())
        row.append(i)
        col.append(i)
        val.append(d)
        for k in offsets:
            j = (i + k) % n if cyclic else i + k
            if j != i and 0 <= j < n:
                row.append(i)
                col.append(j)
                val.append(0.2 * d * (0.1 + rng.random()))
    row, col, val = np.array(row), np.array(col), np.array(val)
    b = dense_of(row, col, val, n) @ rng.standard_normal(n)
    return row, col, val, b


#: off-diagonal pattern of each swept family: (offsets, cyclic)
_DOMINANT_FAMILIES = {
    "tridiagonal": ((-1, 1), False),
    "cyclic_1_5": ((1, 5), True),
}


def test_property_random_dominant_systems_converge():
    """Hypothesis sweep over random row-dominant systems with wildly scaled
    rows (skipped where hypothesis is not installed — the CI solver job
    runs it). A converged report always carries the dense reference's x.

    Tridiagonal: every other perfect matching of the pattern differs from
    the diagonal by disjoint adjacent swaps, each an augmenting 4-cycle, so
    AWAC reaches the maximum-product matching and AWPM static pivoting
    always converges.

    Cyclic (i+1, i+5): the pattern has longer cycles, and AWPM can stop on
    a matching that no 4-cycle improves; static LU then meets pivots near
    1e-4 and refinement can stall above the bar (26 of the 3,600 systems
    with n in 3..14 and seed in 0..299, all at n >= 11, among them (13, 3)
    and (12, 51), the examples pinned below). That failure must
    show as ``report.ok == False``, never as a wrong x, and must be the
    approximation's: AWPM's matching is lighter than the optimum, its dual
    certificate is not tight, and exact-matching pivoting converges."""
    hypothesis = pytest.importorskip("hypothesis")
    st = hypothesis.strategies

    @hypothesis.settings(max_examples=40, deadline=None)
    @hypothesis.given(st.sampled_from(sorted(_DOMINANT_FAMILIES)),
                      st.integers(3, 14), st.integers(0, 10_000))
    @hypothesis.example("cyclic_1_5", 13, 3)
    @hypothesis.example("cyclic_1_5", 12, 51)
    def check(family, n, seed):
        offsets, cyclic = _DOMINANT_FAMILIES[family]
        row, col, val, b = _dominant_system(n, seed, offsets, cyclic)
        rep = solve_linear_system((row, col, val, n), b)
        if family == "tridiagonal":
            assert rep.ok, rep.summary()
        if rep.ok:
            dense = dense_of(row, col, val, n)
            x_ref = np.linalg.solve(dense, b)
            err = np.linalg.norm(rep.x - x_ref) / np.linalg.norm(x_ref)
            assert err <= 100 * np.linalg.cond(dense) * 1e-10
        else:
            exact = solve_linear_system((row, col, val, n), b,
                                        pivoting="reference")
            assert exact.ok, exact.summary()
            assert rep.matching_weight < exact.matching_weight
            assert rep.matching_tight is False

    check()


def test_refinement_converges_in_the_original_frame():
    """Refinement runs on the scaled system but must stop on the ORIGINAL
    system's residual: on this instance the scaled residual reaches 5e-15
    while the original one is still 1.3e-8 (row scalings span 6e9)."""
    row, col, val, b = _dominant_system(9, 1, (1, 5), cyclic=True)
    rep = solve_linear_system((row, col, val, 9), b)
    assert rep.ok, rep.summary()


def test_solver_export_surface():
    expected = [
        "CsrMatrix",
        "LUFactorization",
        "LUStats",
        "PIVOTING_MODES",
        "RefineResult",
        "ScaledPivoting",
        "SolveReport",
        "awpm_pivoting",
        "from_matching",
        "identity_pivoting",
        "lu_solve_once",
        "reference_pivoting",
        "refine",
        "solve_linear_system",
        "sparse_lu",
    ]
    assert sorted(solver.__all__) == expected
    for name in solver.__all__:
        assert hasattr(solver, name)
