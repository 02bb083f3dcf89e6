"""Implicit-adjoint gradient engine correctness (ISSUE 10 tentpole).

The cg tier's ``peak_steady`` gradient rides ``kernels/fused_cg/adjoint``:
forward is the unchanged fused-CG ``while_loop``, backward ONE adjoint CG
solve of the self-adjoint system plus an O(E) residual VJP. These tests
pin it against the two independent references on all four Table-6
systems — the dense tier's ``jax.grad`` (Cholesky, plain autodiff) and
central finite differences — and assert the backward-pass cost contract
(exactly one adjoint row-solve per candidate, via the adjoint stats
registry). A hypothesis test repeats the parity check across random
valid geometries, and the executor's pad-aware value-and-grad mode is
checked to mask padding out of values AND gradients.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (PackageFamily, RCFamilyModel, build_family,
                        make_2p5d_package, optimize_family,
                        package_from_name)
from repro.kernels.fused_cg import adjoint

SYSTEMS = ["2p5d_16", "2p5d_36", "2p5d_64", "3d_16x3"]


def _grad_models(pkg, params=("grid_offsets", "htc_top")):
    fam = PackageFamily(pkg, params=params)
    cg = RCFamilyModel(fam, dtype=jnp.float64, solver="cg")
    dense = RCFamilyModel(fam, dtype=jnp.float64, solver="dense")
    return fam, cg, dense


def _rel(a, b, floor=1e-3):
    return np.abs(a - b) / np.maximum(np.abs(b), floor)


# ---------------------------------------------------------------------------
# cg-grad vs dense-grad vs central FD on the Table-6 systems
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("system", SYSTEMS)
def test_adjoint_grad_matches_dense_and_fd(system):
    pkg, _ = package_from_name(system)
    with jax.enable_x64(True):
        fam, cg, dense = _grad_models(pkg)
        p0 = fam.sample_params(1, seed=5)[0]
        s = len(fam.sym.source_names)
        q = np.full(s, 1.5)

        def peak(model):
            return lambda p: model.peak_steady(p[None], q[None])[0]

        v_cg = float(peak(cg)(jnp.asarray(p0)))
        v_dense = float(peak(dense)(jnp.asarray(p0)))
        assert abs(v_cg - v_dense) < 1e-6

        g_cg = np.asarray(jax.grad(peak(cg))(jnp.asarray(p0)))
        g_dense = np.asarray(jax.grad(peak(dense))(jnp.asarray(p0)))
        assert np.all(np.isfinite(g_cg))
        assert _rel(g_cg, g_dense).max() < 1e-4

        # central finite differences on a parameter subset (first offset
        # + htc_top: one of each parameter class; FD over every param of
        # every system would dominate suite runtime)
        i_htc = fam.param_names.index("htc_top")
        for k in (0, i_htc):
            h = max(1e-7 * abs(float(p0[k])), 1e-9)
            pp, pm = p0.copy(), p0.copy()
            pp[k] += h
            pm[k] -= h
            fd = (peak(cg)(jnp.asarray(pp))
                  - peak(cg)(jnp.asarray(pm))) / (2 * h)
            assert abs(g_cg[k] - fd) <= 1e-4 * max(abs(fd), 1e-3)


# ---------------------------------------------------------------------------
# backward-pass cost contract: ONE adjoint solve per candidate
# ---------------------------------------------------------------------------
def test_backward_is_one_adjoint_solve():
    with jax.enable_x64(True):
        fam, cg, _ = _grad_models(make_2p5d_package(16))
        params = fam.sample_params(3, seed=6)
        q = np.full(16, 2.0)
        adjoint.reset_adjoint_stats()
        vals, grads = cg.peak_steady_and_grad(params, q, tau=0.5)
        assert vals.shape == (3,) and grads.shape == (3, fam.n_params)
        counts = adjoint.solve_counts()
        fwd = counts["rc family peak_steady adjoint CG [forward]"]
        bwd = counts["rc family peak_steady adjoint CG"]
        # one forward row-solve and ONE adjoint row-solve per candidate
        assert fwd["rows"] == 3
        assert bwd["rows"] == 3
        stats = adjoint.last_stats("rc family peak_steady adjoint CG")
        assert stats is not None and bool(np.all(stats.converged))
        assert int(np.max(stats.iterations)) >= 1


# ---------------------------------------------------------------------------
# executor pad masking: padded batches match per-candidate evaluation
# ---------------------------------------------------------------------------
def test_run_value_and_grad_pad_masking():
    """B=5 over chunk_size=2 pads to 6: the pad row (base_params) must
    be evaluated but masked — values/grads of the 5 real rows identical
    to the unchunked, unpadded batch."""
    with jax.enable_x64(True):
        fam = PackageFamily(make_2p5d_package(16),
                            params=("grid_offsets",))
        plain = RCFamilyModel(fam, dtype=jnp.float64, solver="cg")
        chunked = RCFamilyModel(fam, dtype=jnp.float64, solver="cg",
                                chunk_size=2)
        params = fam.sample_params(5, seed=7)
        q = np.full(16, 2.0)
        v0, g0 = plain.peak_steady_and_grad(params, q, tau=0.5)
        v1, g1 = chunked.peak_steady_and_grad(params, q, tau=0.5)
        assert v1.shape == (5,) and g1.shape == (5, fam.n_params)
        np.testing.assert_allclose(np.asarray(v1), np.asarray(v0),
                                   rtol=1e-10, atol=1e-10)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g0),
                                   rtol=1e-8, atol=1e-12)


# ---------------------------------------------------------------------------
# ROM transient objective: reverse-differentiable rollout vs FD
# ---------------------------------------------------------------------------
def test_rom_transient_grad_matches_fd():
    with jax.enable_x64(True):
        fam = PackageFamily(make_2p5d_package(16),
                            params=("grid_offsets",))
        rom = build_family(fam, "rom", dtype=jnp.float64)
        p0 = fam.sample_params(1, seed=8)
        T, dt = 12, 0.01
        qt = np.tile(np.full(16, 2.0), (T, 1)) \
            * np.linspace(0.5, 1.5, T)[:, None]
        vals, grads = rom.peak_transient_and_grad(p0, qt, dt)
        assert vals.shape == (1,) and grads.shape == (1, fam.n_params)
        assert np.all(np.isfinite(np.asarray(grads)))
        k = 0
        h = 1e-6
        pp, pm = p0.copy(), p0.copy()
        pp[0, k] += h
        pm[0, k] -= h
        fd = (float(rom.peak_transient(pp, qt, dt)[0])
              - float(rom.peak_transient(pm, qt, dt)[0])) / (2 * h)
        assert abs(float(grads[0, k]) - fd) <= 1e-4 * max(abs(fd), 1e-3)


# ---------------------------------------------------------------------------
# optimizer: improves on its starts, stays in-family, respects budget
# ---------------------------------------------------------------------------
def test_optimize_family_improves_and_stays_valid():
    with jax.enable_x64(True):
        fam = PackageFamily(make_2p5d_package(16),
                            params=("grid_offsets",))
        model = RCFamilyModel(fam, dtype=jnp.float64, solver="cg")
        q = np.full(16, 0.4)
        q[[5, 6, 9, 10]] = 3.0
        base = float(model.peak_steady(fam.base_params()[None],
                                       q[None])[0])
        res = optimize_family(model, q, n_starts=4, method="adam",
                              steps=10, budget=120, seed=0)
        assert res.best_value <= base + 1e-9
        assert res.n_solve_equiv <= 120
        fam.validate_params(res.best_params)  # raises if degenerate
        lo, hi = fam.param_bounds().T
        assert np.all(res.best_params >= lo - 1e-12)
        assert np.all(res.best_params <= hi + 1e-12)


def test_optimize_family_lbfgs_avoids_degenerate_corner():
    """Regression: L-BFGS once walked to a param_bounds() corner where
    two cut lines jointly collide — CG broke down on the singular system
    and reported the ambient temperature as a bogus 'optimum'. The
    frac-shrunk projection box plus the non-finite guard must keep every
    reported start value physical (above ambient + the mean rise)."""
    with jax.enable_x64(True):
        fam = PackageFamily(make_2p5d_package(16),
                            params=("grid_offsets",))
        model = RCFamilyModel(fam, dtype=jnp.float64, solver="cg")
        q = np.full(16, 0.4)
        q[[5, 6, 9, 10]] = 3.0
        res = optimize_family(model, q, n_starts=4, method="lbfgs",
                              steps=8, budget=200, seed=0)
        t_amb = fam.template.t_ambient
        assert np.all(res.start_values > t_amb + 1.0)
        fam.validate_params(res.best_params)


# ---------------------------------------------------------------------------
# hypothesis: grad parity across random valid geometries
# ---------------------------------------------------------------------------
try:  # module-level importorskip would skip the NON-hypothesis tests too
    from hypothesis import given, settings
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False


def _grad_parity_one(pkg):
    with jax.enable_x64(True):
        fam, cg, dense = _grad_models(pkg, params=("grid_offsets",))
        if fam.n_params == 0:  # single-chiplet: no offsets to move
            return
        p0 = jnp.asarray(fam.sample_params(1, seed=3)[0])
        s = len(fam.sym.source_names)
        q = np.full(s, 1.0)

        def peak(model):
            return lambda p: model.peak_steady(p[None], q[None])[0]

        g_cg = np.asarray(jax.grad(peak(cg))(p0))
        g_dense = np.asarray(jax.grad(peak(dense))(p0))
        assert np.all(np.isfinite(g_cg))
        assert _rel(g_cg, g_dense).max() < 1e-4


if _HAVE_HYPOTHESIS:
    from test_property import packages

    @given(packages())
    @settings(max_examples=8, deadline=None)
    def test_adjoint_grad_parity_random_geometries(pkg):
        _grad_parity_one(pkg)
else:
    @pytest.mark.skip(reason="property tests need the 'dev' extra")
    def test_adjoint_grad_parity_random_geometries():
        pass
