"""Thermal RC vs FVM golden reference (paper Table 8 accuracy class),
plus the solver-tier cross-regressions: the matrix-free "cg" tier must
reproduce the "dense" tier on every Table-6 system."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (build, make_2p5d_package, make_3d_package,
                        package_from_name)

from repro.core.workloads import wl1


@pytest.fixture(scope="module")
def small_pkg():
    return make_2p5d_package(4)


@pytest.fixture(scope="module")
def small_fvm(small_pkg):
    return build(small_pkg, "fvm", dx_target=0.25e-3, cg_tol=1e-7)


def test_steady_state_accuracy(small_pkg, small_fvm):
    q = np.full(4, 3.0)
    rc = build(small_pkg, "rc")
    t_rc = np.asarray(rc.observe(rc.steady_state(q)))
    t_fv = np.asarray(small_fvm.observe(small_fvm.steady_state(q)))
    assert np.all(t_rc > small_pkg.t_ambient + 10)  # heat actually flows
    assert np.abs(t_rc - t_fv).max() < 1.7  # paper's RC error bound


def test_transient_accuracy(small_pkg, small_fvm):
    dt = 0.01
    q = wl1(4, dt=dt, t_stress=1.5, t_prbs=1.5, t_cool=1.0, seed=3)
    rc = build(small_pkg, "rc")
    obs_rc = np.asarray(rc.make_simulator(dt)(rc.zero_state(), q))
    obs_fv = np.asarray(small_fvm.make_simulator(dt)(
        small_fvm.zero_state(), q))
    mae = np.abs(obs_rc - obs_fv).mean()
    assert mae < 1.7, mae  # paper bound for UNTUNED capacitance


def test_3d_builds_and_steady():
    pkg = make_3d_package(4, tiers=2)
    rc = build(pkg, "rc")
    q = np.full(8, 1.2)
    temps = np.asarray(rc.observe(rc.steady_state(q)))
    assert temps.shape == (8,)
    assert np.all(temps > pkg.t_ambient)
    # lower-tier chiplets run hotter (heat must cross upper tier to lid)
    lower = [i for i, t in enumerate(rc.tags) if "_t0" in t]
    upper = [i for i, t in enumerate(rc.tags) if "_t1" in t]
    assert temps[lower].mean() > temps[upper].mean() - 1e-3


def test_heatmap_shape(small_pkg):
    rc = build(small_pkg, "rc")
    theta = rc.steady_state(np.full(4, 3.0))
    vals, rects = rc.layer_heatmap(theta, layer_idx=4)
    assert len(vals) == len(rects) > 0


# ---------------------------------------------------------------------------
# Solver-tier cross-regressions (PR 3): "cg" vs "dense" on Table-6 systems
# ---------------------------------------------------------------------------
def _cross_solver_err(system, p_chip=3.0):
    pkg, s = package_from_name(system)
    q = np.full(s, p_chip)
    with jax.enable_x64(True):
        dense = build(pkg, "rc", dtype=jnp.float64, solver="dense")
        cg = build(pkg, "rc", dtype=jnp.float64, solver="cg")
        t_dense = np.asarray(dense.observe(dense.steady_state(q)))
        t_cg = np.asarray(cg.observe(cg.steady_state(q)))
    return np.abs(t_dense - t_cg).max()


@pytest.mark.parametrize("system", ["2p5d_16", "2p5d_36", "2p5d_64",
                                    "3d_16x3"])
def test_steady_cross_solver_table6(system):
    assert _cross_solver_err(system) < 1e-6


@pytest.mark.slow
def test_steady_cross_solver_2p5d_256():
    """The >=4k-node system of the sparse_solver benchmark (8196 nodes):
    the CG tier that beats dense on wall clock also matches it."""
    assert _cross_solver_err("2p5d_256") < 1e-6


@pytest.mark.parametrize("system", ["2p5d_16", "3d_4x2"])
def test_refined_f32_cg_matches_f64_dense(system):
    """Mixed-precision iterative refinement: the DEFAULT f32 cg steady
    solve (f64 host residuals + f32 device correction CG) reproduces the
    f64 dense tier to <=1e-6 degC WITHOUT JAX_ENABLE_X64 — the
    'f64-free CG' ROADMAP headroom item."""
    pkg, s = package_from_name(system)
    q = np.full(s, 3.0)
    with jax.enable_x64(True):
        dense = build(pkg, "rc", dtype=jnp.float64, solver="dense")
        ref = np.asarray(dense.observe(dense.steady_state(q)))
    cg = build(pkg, "rc", solver="cg")  # default f32, x64 NOT enabled
    t_cg = cg.observe(cg.steady_state(q))
    # the refined state stays float64 end to end through observe
    assert isinstance(t_cg, np.ndarray) and t_cg.dtype == np.float64
    assert np.abs(t_cg - ref).max() < 1e-6


def test_refined_cg_threads_through_dss_steady():
    """build(pkg, "dss", solver="cg") rides the refined closure: its f32
    steady state now lands within the f32 representation floor of the
    f64 dense fixed point (no x64 anywhere)."""
    pkg = make_2p5d_package(4)
    q = np.full(4, 3.0)
    with jax.enable_x64(True):
        dense = build(pkg, "dss", ts=0.01, dtype=jnp.float64,
                      solver="dense")
        ref = np.asarray(dense.observe(dense.steady_state(q)))
    cg = build(pkg, "dss", ts=0.01, solver="cg")
    t_cg = np.asarray(cg.observe(cg.steady_state(q)))
    assert np.abs(t_cg - ref).max() < 1e-4  # f32 state-cast floor


def test_transient_cross_solver(small_pkg):
    """BE and TRAP integrators: matrix-free twin vs dense factorization."""
    dt = 0.01
    q = np.full((40, 4), 2.0)
    with jax.enable_x64(True):
        dense = build(small_pkg, "rc", dtype=jnp.float64, solver="dense")
        cg = build(small_pkg, "rc", dtype=jnp.float64, solver="cg")
        for method in ("be_chol", "trap"):
            od = np.asarray(dense.make_simulator(dt, method=method)(
                dense.zero_state(), q))
            oc = np.asarray(cg.make_simulator(dt, method=method)(
                cg.zero_state(), q))
            assert np.abs(od - oc).max() < 1e-6, method


def test_dss_steady_cross_solver(small_pkg):
    """DSS ZOH fixed point vs the matrix-free continuous fixed point."""
    q = np.full(4, 3.0)
    with jax.enable_x64(True):
        dense = build(small_pkg, "dss", ts=0.01, dtype=jnp.float64,
                      solver="dense")
        cg = build(small_pkg, "dss", ts=0.01, dtype=jnp.float64,
                   solver="cg")
        td = np.asarray(dense.observe(dense.steady_state(q)))
        tc = np.asarray(cg.observe(cg.steady_state(q)))
    assert np.abs(td - tc).max() < 1e-6


def test_fvm_dense_solver_matches_cg(small_pkg):
    """Coarse-grid dense FVM (validation tier) vs the native stencil CG."""
    q = np.full(4, 3.0)
    cg = build(small_pkg, "fvm", dx_target=1.5e-3, cg_tol=1e-7)
    dense = build(small_pkg, "fvm", dx_target=1.5e-3, solver="dense")
    tc = np.asarray(cg.observe(cg.steady_state(q)))
    td = np.asarray(dense.observe(dense.steady_state(q)))
    assert np.abs(td - tc).max() < 5e-2  # f32 stencil-CG tolerance class
    dt, steps = 0.01, 15
    qt = np.full((steps, 4), 2.0)
    oc = np.asarray(cg.make_simulator(dt)(cg.zero_state(), qt))
    od = np.asarray(dense.make_simulator(dt)(dense.zero_state(), qt))
    assert np.abs(od - oc).max() < 5e-3


@pytest.mark.slow
def test_fine_fvm_rc_agreement():
    """Fine-grid (0.25 mm) FVM reference vs the tuned RC model on the
    16-chiplet Table-6 system — the accuracy anchor of the ladder."""
    pkg = make_2p5d_package(16)
    q = np.full(16, 3.0)
    fvm = build(pkg, "fvm", dx_target=0.25e-3, cg_tol=1e-7)
    rc = build(pkg, "rc", solver="auto")
    t_fv = np.asarray(fvm.observe(fvm.steady_state(q)))
    t_rc = np.asarray(rc.observe(rc.steady_state(q)))
    assert np.abs(t_rc - t_fv).max() < 1.7  # paper's RC error bound
