"""Parity and convergence tests for the fused CG-step kernel
(``kernels/fused_cg``): ``fused_cg_solve`` and ``pcg_loop`` on the COO
matvec kernel, each on both backends, against the dense oracle, the
Pallas kernels in interpret mode on CPU, stats/converged-flag behavior,
and cg-tier-vs-dense-tier agreement on random geometries."""
import warnings

import jax
import jax.experimental
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ThermalRCModel, build_network, make_2p5d_package,
                        package_from_name)
from repro.kernels.coo_matvec.ops import coo_matvec, coo_plan
from repro.kernels.fused_cg import ops
from repro.kernels.fused_cg.ops import (fused_cg_plan, fused_cg_solve,
                                        pcg_loop)
from repro.kernels.fused_cg.ref import dense_matrix_ref, dense_solve_ref

# (n_nodes, n_edge_pairs): ragged sizes spanning sub-tile to multi-tile
# edge counts and sub-lane to multi-lane node counts
SIZES = [(17, 9), (37, 230), (129, 511), (129, 513), (300, 2048),
         (564, 5000)]

# (solver, backend): ``fused_cg_solve`` on its two forms, and
# ``pcg_loop`` on the operator of the family dense tier — the COO matvec
# kernel in its Pallas (interpret) and XLA forms — with Jacobi
PAIRINGS = [("fused", "interpret"), ("fused", "xla"),
            ("pcg_loop", "interpret"), ("pcg_loop", "xla")]


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


def solve(solver, backend, rows, cols, n, diag, gvals, rhs, *, tol,
          maxiter, dtype=None):
    """``(diag(diag) - offdiag(gvals)) x = rhs`` by ``fused_cg_solve`` or
    by ``pcg_loop`` with the COO matvec and Jacobi; rhs (N,) or (B, N).
    Returns ``(x, CGStats)`` shaped like ``fused_cg_solve``'s."""
    diag, gvals, rhs = (jnp.asarray(a, dtype) for a in (diag, gvals, rhs))
    if solver == "fused":
        return fused_cg_solve(fused_cg_plan(rows, cols, n), diag, gvals,
                              rhs, tol=tol, maxiter=maxiter,
                              backend=backend)
    plan = coo_plan(rows, cols, n)
    rhs2 = rhs.reshape(-1, n)
    x, stats = pcg_loop(
        lambda x: diag * x - coo_matvec(plan, gvals, x, backend=backend),
        lambda r: r / diag, rhs2, jnp.zeros_like(rhs2), tol, maxiter)
    return (x.reshape(rhs.shape),
            ops.CGStats(*(s.reshape(rhs.shape[:-1]) for s in stats)))


@pytest.mark.parametrize("n,e", SIZES)
@pytest.mark.parametrize("solver,backend", PAIRINGS)
def test_parity_vs_dense_oracle_f64(n, e, solver, backend):
    rows, cols, gvals, diag = random_spd_system(n, e, seed=n + e)
    rhs = np.random.default_rng(1).normal(size=n)
    ref = dense_solve_ref(diag, gvals, rows, cols, rhs)
    with jax.enable_x64(True):
        x, stats = solve(solver, backend, rows, cols, n, diag, gvals, rhs,
                         tol=1e-12, maxiter=4 * n)
        assert np.asarray(stats.converged).all()
        np.testing.assert_allclose(np.asarray(x), ref, atol=1e-8)


@pytest.mark.parametrize("b", [1, 3, 8, 11])
@pytest.mark.parametrize("solver,backend", PAIRINGS)
def test_batched_rhs_parity(b, solver, backend):
    n, e = 129, 513
    rows, cols, gvals, diag = random_spd_system(n, e, seed=7)
    rhs = np.random.default_rng(2).normal(size=(b, n))
    ref = dense_solve_ref(diag, gvals, rows, cols, rhs)
    with jax.enable_x64(True):
        x, stats = solve(solver, backend, rows, cols, n, diag, gvals, rhs,
                         tol=1e-12, maxiter=4 * n)
    assert x.shape == (b, n)
    assert np.asarray(stats.iterations).shape == (b,)
    assert np.asarray(stats.converged).all()
    np.testing.assert_allclose(np.asarray(x), ref, atol=1e-8)


@pytest.mark.parametrize("solver,backend", PAIRINGS)
def test_f32_parity_and_stats(solver, backend):
    """f32 runs converge to the f32 residual class and report it."""
    n, e = 300, 2048
    rows, cols, gvals, diag = random_spd_system(n, e, seed=3)
    rhs = np.abs(np.random.default_rng(3).normal(size=n))
    ref = dense_solve_ref(diag, gvals, rows, cols, rhs)
    tol = 1e-5
    x, stats = solve(solver, backend, rows, cols, n, diag, gvals, rhs,
                     tol=tol, maxiter=1000, dtype=jnp.float32)
    assert x.dtype == jnp.float32
    assert np.asarray(stats.converged).all()
    assert float(stats.residual) <= tol
    assert 0 < int(stats.iterations) < 1000
    rel = np.abs(np.asarray(x) - ref).max() / np.abs(ref).max()
    assert rel < 1e-4


def test_real_table6_pattern_matches_dense_f64():
    """Every pairing (the Pallas kernels in interpret mode) on a real
    Table-6 package pattern agrees with the dense f64 oracle to
    <=1e-6."""
    net = build_network(make_2p5d_package(16))
    diag = net.neg_g_diag()
    q = np.full(len(net.grid.source_names), 2.0)
    rhs = net.P @ q
    ref = dense_solve_ref(diag, net.gvals, net.rows, net.cols, rhs)
    with jax.enable_x64(True):
        for solver, backend in PAIRINGS:
            x, stats = solve(solver, backend, net.rows, net.cols, net.n,
                             diag, net.gvals, rhs, tol=1e-12, maxiter=5000)
            assert np.asarray(stats.converged).all(), (solver, backend)
            assert np.abs(np.asarray(x) - ref).max() < 1e-6, \
                (solver, backend)


def test_empty_pattern_degenerates_to_diagonal_solve():
    n = 40
    diag = np.linspace(1.0, 3.0, n)
    rhs = np.random.default_rng(5).normal(size=n)
    empty = np.zeros(0, np.int32)
    with jax.enable_x64(True):
        for solver, backend in PAIRINGS:
            x, stats = solve(solver, backend, empty, empty, n, diag,
                             np.zeros(0), rhs, tol=1e-12, maxiter=50)
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
                               tol=1e-12, maxiter=1000,
                               backend="interpret")
        # warm restart from the converged answer: 0 further iterations
        x2, st2 = fused_cg_solve(plan, jnp.asarray(diag),
                                 jnp.asarray(gvals), jnp.asarray(rhs),
                                 x0=x, tol=1e-10, maxiter=1000,
                                 backend="interpret")
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
                              tol=1e-6, maxiter=2, backend="xla")
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
                                 backend="xla")
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


@pytest.fixture(scope="module")
def table6_plans():
    return {name: fused_cg_plan(net.rows, net.cols, net.n)
            for name in ("2p5d_64", "3d_16x3", "2p5d_256")
            for net in [build_network(package_from_name(name)[0])]}


@pytest.mark.parametrize("name,sweep_block", [
    ("2p5d_64", 256), ("3d_16x3", 256), ("2p5d_256", 64)])
def test_batch_block_rule(table6_plans, name, sweep_block):
    """The batch block is the largest multiple of SUBLANE that divides the
    batch padded to SUBLANE, within the cap and the VMEM budget; it is
    SUBLANE for batches of at most SUBLANE rows (the program single
    solves ran before the rule)."""
    plan = table6_plans[name]
    for b in range(1, ops.SUBLANE + 1):
        assert ops.fused_cg_block(b, plan, 4)[0] == ops.SUBLANE
    for b in (9, 11, 48, 100, 1000, 2048, 4096, 10000):
        block, limit = ops.fused_cg_block(b, plan, 4)
        b_pad = -(-b // ops.SUBLANE) * ops.SUBLANE
        assert block % ops.SUBLANE == 0 and b_pad % block == 0, (b, block)
        assert block <= ops.BLOCK_CAP
        need = ops.fused_cg_vmem_bytes(block, plan, 4)
        assert need <= ops.VMEM_BUDGET
        assert need < limit <= ops._VMEM_CEIL
        # the largest such block: the next divisor up is over the cap or
        # the budget
        bigger = [r for r in range(block + ops.SUBLANE, b_pad + 1,
                                   ops.SUBLANE) if b_pad % r == 0]
        if bigger:
            assert (bigger[0] > ops.BLOCK_CAP or ops.fused_cg_vmem_bytes(
                bigger[0], plan, 4) > ops.VMEM_BUDGET), (b, block)
    assert ops.fused_cg_block(2048, plan, 4)[0] == sweep_block


def test_wide_block_parity_and_iterations():
    """A batch whose block exceeds SUBLANE (the kernel in interpret mode)
    matches the dense f64 oracle, and every row spends the iterations it
    spends on the fused XLA path."""
    n, e, b = 129, 513, 48
    rows, cols, gvals, diag = random_spd_system(n, e, seed=21)
    rhs = np.random.default_rng(5).normal(size=(b, n))
    ref = dense_solve_ref(diag, gvals, rows, cols, rhs)
    with jax.enable_x64(True):
        plan = fused_cg_plan(rows, cols, n)
        assert ops.fused_cg_block(b, plan, 8)[0] > ops.SUBLANE
        out = {backend: fused_cg_solve(plan, jnp.asarray(diag),
                                       jnp.asarray(gvals), jnp.asarray(rhs),
                                       tol=1e-12, maxiter=4 * n,
                                       backend=backend)
               for backend in ("interpret", "xla")}
    x, stats = out["interpret"]
    assert np.asarray(stats.converged).all()
    np.testing.assert_allclose(np.asarray(x), ref, atol=1e-8)
    np.testing.assert_array_equal(np.asarray(stats.iterations),
                                  np.asarray(out["xla"][1].iterations))


# --------------------------------------------------------------------------
# hypothesis property: the cg and dense tiers agree on random geometries
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
    def test_cg_tier_matches_dense_tier_on_random_geometries(pkg, p_chip):
        """The cg tier's steady observations agree with the dense
        tier's to <=1e-6 degC on random valid geometries (f64)."""
        with jax.enable_x64(True):
            net = build_network(pkg)
            temps = {}
            for solver in ("cg", "dense"):
                m = ThermalRCModel(net, dtype=jnp.float64, solver=solver)
                q = np.full(len(m.source_names), p_chip)
                temps[solver] = np.asarray(m.observe(m.steady_state(q)))
        assert np.abs(temps["cg"] - temps["dense"]).max() < 1e-6
else:  # keep the suite honest about what was skipped
    @pytest.mark.skip(reason="property tests need the 'dev' extra")
    def test_cg_tier_matches_dense_tier_on_random_geometries():
        pass
