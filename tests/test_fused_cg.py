"""Parity and convergence tests for the fused CG-step kernel
(``kernels/fused_cg``): every impl x backend pairing against the dense
oracle, the Pallas kernel in interpret mode on CPU, stats/converged-flag
behavior, and fused-vs-unfused agreement on random geometries."""
import warnings

import jax
import jax.experimental
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ThermalRCModel, build_network, make_2p5d_package
from repro.kernels.fused_cg import ops
from repro.kernels.fused_cg.ops import (fused_cg_plan, fused_cg_solve,
                                        pcg_loop, resolve_cg_impl)
from repro.kernels.fused_cg.ref import dense_matrix_ref, dense_solve_ref

# (n_nodes, n_edge_pairs): ragged sizes spanning sub-tile to multi-tile
# edge counts and sub-lane to multi-lane node counts
SIZES = [(17, 9), (37, 230), (129, 511), (129, 513), (300, 2048),
         (564, 5000)]

PAIRINGS = [("fused", "interpret"), ("fused", "xla"),
            ("unfused", "interpret"), ("unfused", "xla")]


def random_spd_system(n, e_half, seed=0):
    """Random symmetric diagonally-dominant system in the solver's form
    ``A = diag(diag) - offdiag(gvals)`` (gvals > 0)."""
    rng = np.random.default_rng(seed)
    r = rng.integers(0, n, e_half)
    c = rng.integers(0, n, e_half)
    keep = r != c
    r, c = r[keep], c[keep]
    rows = np.concatenate([r, c])
    cols = np.concatenate([c, r])
    gv = np.abs(rng.normal(1.0, 0.3, r.size)) + 0.05
    gvals = np.concatenate([gv, gv])
    diag = np.zeros(n)
    np.add.at(diag, rows, gvals)
    diag += rng.uniform(0.5, 2.0, n)  # strict dominance -> SPD
    return rows, cols, gvals, diag


@pytest.mark.parametrize("n,e", SIZES)
@pytest.mark.parametrize("impl,backend", PAIRINGS)
def test_parity_vs_dense_oracle_f64(n, e, impl, backend):
    rows, cols, gvals, diag = random_spd_system(n, e, seed=n + e)
    rhs = np.random.default_rng(1).normal(size=n)
    ref = dense_solve_ref(diag, gvals, rows, cols, rhs)
    with jax.enable_x64(True):
        plan = fused_cg_plan(rows, cols, n)
        x, stats = fused_cg_solve(plan, jnp.asarray(diag),
                                  jnp.asarray(gvals), jnp.asarray(rhs),
                                  tol=1e-12, maxiter=4 * n,
                                  impl=impl, backend=backend)
        assert np.asarray(stats.converged).all()
        np.testing.assert_allclose(np.asarray(x), ref, atol=1e-8)


@pytest.mark.parametrize("b", [1, 3, 8, 11])
@pytest.mark.parametrize("impl,backend", PAIRINGS)
def test_batched_rhs_parity(b, impl, backend):
    n, e = 129, 513
    rows, cols, gvals, diag = random_spd_system(n, e, seed=7)
    rhs = np.random.default_rng(2).normal(size=(b, n))
    ref = dense_solve_ref(diag, gvals, rows, cols, rhs)
    with jax.enable_x64(True):
        plan = fused_cg_plan(rows, cols, n)
        x, stats = fused_cg_solve(plan, jnp.asarray(diag),
                                  jnp.asarray(gvals), jnp.asarray(rhs),
                                  tol=1e-12, maxiter=4 * n,
                                  impl=impl, backend=backend)
    assert x.shape == (b, n)
    assert np.asarray(stats.iterations).shape == (b,)
    assert np.asarray(stats.converged).all()
    np.testing.assert_allclose(np.asarray(x), ref, atol=1e-8)


@pytest.mark.parametrize("impl,backend", PAIRINGS)
def test_f32_parity_and_stats(impl, backend):
    """f32 runs converge to the f32 residual class and report it."""
    n, e = 300, 2048
    rows, cols, gvals, diag = random_spd_system(n, e, seed=3)
    rhs = np.abs(np.random.default_rng(3).normal(size=n))
    ref = dense_solve_ref(diag, gvals, rows, cols, rhs)
    plan = fused_cg_plan(rows, cols, n)
    tol = 1e-5
    x, stats = fused_cg_solve(plan, jnp.asarray(diag, jnp.float32),
                              jnp.asarray(gvals, jnp.float32),
                              jnp.asarray(rhs, jnp.float32),
                              tol=tol, maxiter=1000,
                              impl=impl, backend=backend)
    assert x.dtype == jnp.float32
    assert np.asarray(stats.converged).all()
    assert float(stats.residual) <= tol
    assert 0 < int(stats.iterations) < 1000
    rel = np.abs(np.asarray(x) - ref).max() / np.abs(ref).max()
    assert rel < 1e-4


def test_real_table6_pattern_matches_dense_f64():
    """The fused kernel (interpret mode) on a real Table-6 package
    pattern agrees with the dense f64 oracle to <=1e-6."""
    net = build_network(make_2p5d_package(16))
    diag = net.neg_g_diag()
    q = np.full(len(net.grid.source_names), 2.0)
    rhs = net.P @ q
    ref = dense_solve_ref(diag, net.gvals, net.rows, net.cols, rhs)
    with jax.enable_x64(True):
        plan = fused_cg_plan(net.rows, net.cols, net.n)
        for impl, backend in PAIRINGS:
            x, stats = fused_cg_solve(
                plan, jnp.asarray(diag), jnp.asarray(net.gvals),
                jnp.asarray(rhs), tol=1e-12, maxiter=5000,
                impl=impl, backend=backend)
            assert np.asarray(stats.converged).all(), (impl, backend)
            assert np.abs(np.asarray(x) - ref).max() < 1e-6, \
                (impl, backend)


def test_empty_pattern_degenerates_to_diagonal_solve():
    n = 40
    diag = np.linspace(1.0, 3.0, n)
    rhs = np.random.default_rng(5).normal(size=n)
    with jax.enable_x64(True):
        plan = fused_cg_plan(np.zeros(0, np.int32), np.zeros(0, np.int32),
                             n)
        for impl, backend in PAIRINGS:
            x, stats = fused_cg_solve(plan, jnp.asarray(diag),
                                      jnp.zeros((0,), jnp.float64),
                                      jnp.asarray(rhs), tol=1e-12,
                                      maxiter=50, impl=impl,
                                      backend=backend)
            np.testing.assert_allclose(np.asarray(x), rhs / diag,
                                       atol=1e-12)


def test_warm_start_and_zero_rhs_rows():
    """x0 warm start short-circuits; an all-zero rhs row converges to
    zero immediately without 0/0 poisoning its live-mask."""
    n, e = 129, 511
    rows, cols, gvals, diag = random_spd_system(n, e, seed=11)
    rhs = np.random.default_rng(6).normal(size=(3, n))
    rhs[1] = 0.0
    with jax.enable_x64(True):
        plan = fused_cg_plan(rows, cols, n)
        x, st = fused_cg_solve(plan, jnp.asarray(diag),
                               jnp.asarray(gvals), jnp.asarray(rhs),
                               tol=1e-12, maxiter=1000, impl="fused",
                               backend="interpret")
        # warm restart from the converged answer: 0 further iterations
        x2, st2 = fused_cg_solve(plan, jnp.asarray(diag),
                                 jnp.asarray(gvals), jnp.asarray(rhs),
                                 x0=x, tol=1e-10, maxiter=1000,
                                 impl="fused", backend="interpret")
    assert np.abs(np.asarray(x)[1]).max() == 0.0
    assert np.asarray(st.converged).all()
    assert np.asarray(st2.iterations).max() == 0
    np.testing.assert_allclose(np.asarray(x2), np.asarray(x), atol=1e-9)


def test_maxiter_cap_sets_converged_false_and_model_warns():
    n, e = 300, 2048
    rows, cols, gvals, diag = random_spd_system(n, e, seed=13)
    rhs = np.random.default_rng(7).normal(size=n)
    plan = fused_cg_plan(rows, cols, n)
    _, stats = fused_cg_solve(plan, jnp.asarray(diag, jnp.float32),
                              jnp.asarray(gvals, jnp.float32),
                              jnp.asarray(rhs, jnp.float32),
                              tol=1e-6, maxiter=2, impl="fused",
                              backend="xla")
    assert not np.asarray(stats.converged).any()
    assert int(np.asarray(stats.iterations)) == 2
    # ... and the model-level steady solve surfaces it host-side
    model = ThermalRCModel(build_network(make_2p5d_package(16)),
                           solver="cg", cg_maxiter=2, refine_passes=0)
    ops.reset_unconverged_counts()  # re-arm the one-shot per-site warning
    with pytest.warns(RuntimeWarning, match="iteration cap"):
        model.steady_state(np.full(len(model.source_names), 2.0))
    assert model.last_cg_stats is not None
    assert not bool(np.asarray(model.last_cg_stats.converged).all())
    # rate limit: the same site warns once per process; repeats only count
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        model.steady_state(np.full(len(model.source_names), 2.0))
    assert ops.unconverged_counts()["rc steady CG"] >= 2


def test_model_steady_records_stats():
    model = ThermalRCModel(build_network(make_2p5d_package(16)),
                           solver="cg")
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        model.steady_state(np.full(len(model.source_names), 2.0))
    st = model.last_cg_stats
    assert st is not None and bool(np.asarray(st.converged).all())
    assert int(np.asarray(st.iterations)) > 0
    assert float(np.asarray(st.residual)) <= model.refine_rtol


def test_pcg_loop_matches_fused_jacobi():
    """The generic callable-matvec loop (dense-tier family path) and the
    fused driver agree when handed the same Jacobi-preconditioned
    system."""
    n, e = 129, 511
    rows, cols, gvals, diag = random_spd_system(n, e, seed=17)
    rhs = np.random.default_rng(8).normal(size=(4, n))
    with jax.enable_x64(True):
        plan = fused_cg_plan(rows, cols, n)
        xf, stf = fused_cg_solve(plan, jnp.asarray(diag),
                                 jnp.asarray(gvals), jnp.asarray(rhs),
                                 tol=1e-11, maxiter=1000,
                                 impl="fused", backend="xla")
        a = jnp.asarray(dense_matrix_ref(diag, gvals, rows, cols, n))

        def matvec(x):
            return x @ a.T

        xg, stg = pcg_loop(matvec, lambda r: r / jnp.asarray(diag),
                           jnp.asarray(rhs),
                           jnp.zeros_like(jnp.asarray(rhs)),
                           1e-11, 1000)
    assert np.asarray(stf.converged).all() and \
        np.asarray(stg.converged).all()
    np.testing.assert_allclose(np.asarray(xf), np.asarray(xg), atol=1e-7)


def test_resolve_cg_impl():
    assert resolve_cg_impl("auto") == "fused"
    assert resolve_cg_impl("fused") == "fused"
    assert resolve_cg_impl("unfused") == "unfused"
    with pytest.raises(ValueError, match="cg_impl"):
        resolve_cg_impl("bogus")


# --------------------------------------------------------------------------
# hypothesis property: fused and unfused agree on random geometries
# (hypothesis is a dev-only extra; this block auto-skips without it, the
# parity tests above always run)
# --------------------------------------------------------------------------
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - dev extra absent in CI base image
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    from repro.core import make_3d_package

    @st.composite
    def packages(draw):
        kind = draw(st.sampled_from(["2p5d", "3d"]))
        n_side = draw(st.sampled_from([1, 2, 3]))
        htc = draw(st.floats(500.0, 20000.0))
        funnel = draw(st.booleans())
        if kind == "3d":
            tiers = draw(st.sampled_from([2, 3]))
            return make_3d_package(n_side * n_side, tiers=tiers,
                                   htc_top=htc, funnel=funnel)
        return make_2p5d_package(n_side * n_side, htc_top=htc,
                                 funnel=funnel)

    @given(packages(), st.floats(0.3, 4.0))
    @settings(max_examples=8, deadline=None)
    def test_fused_matches_unfused_on_random_geometries(pkg, p_chip):
        """Fused and unfused CG steady observations agree <=1e-6 degC
        on random valid geometries (f64)."""
        with jax.enable_x64(True):
            net = build_network(pkg)
            temps = {}
            for impl in ("fused", "unfused"):
                m = ThermalRCModel(net, dtype=jnp.float64, solver="cg",
                                   cg_impl=impl)
                q = np.full(len(m.source_names), p_chip)
                temps[impl] = np.asarray(m.observe(m.steady_state(q)))
        assert np.abs(temps["fused"] - temps["unfused"]).max() < 1e-6
else:  # keep the suite honest about what was skipped
    @pytest.mark.skip(reason="property tests need the 'dev' extra")
    def test_fused_matches_unfused_on_random_geometries():
        pass
