"""Batched design-space API: PackageFamily + build_family vs per-package
build() loops (PR 2 tentpole). The batched numeric phase must reproduce
the host per-candidate path to solver tolerance on Table-6 systems."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (PackageFamily, TopologyError,
                        available_family_fidelities, build, build_family,
                        discretize, make_2p5d_package, make_3d_package)


@pytest.fixture(scope="module")
def fam16():
    return PackageFamily(make_2p5d_package(16),
                         params=("grid_offsets", "htc_top"))


def _loop_steady(family, params, q, fidelity="rc", **opts):
    out = []
    for b in range(params.shape[0]):
        m = build(family.instantiate(params[b]), fidelity, **opts)
        out.append(np.asarray(m.observe(m.steady_state(q[b]))))
    return np.stack(out)


# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------
def test_family_param_layout(fam16):
    assert fam16.n_params == 9  # 4 column dx + 4 row dy + htc_top
    assert fam16.param_names[:2] == ["grid_dx:0", "grid_dx:1"]
    assert fam16.param_names[-1] == "htc_top"
    base = fam16.base_params()
    assert np.all(base[:8] == 0.0)
    assert base[8] == fam16.template.htc_top


def test_base_params_reproduce_template(fam16):
    g0 = discretize(fam16.template)
    g1 = discretize(fam16.instantiate(fam16.base_params()))
    for f in ("x0", "x1", "y0", "y1", "lz"):
        np.testing.assert_array_equal(getattr(g0, f), getattr(g1, f))
    c = fam16.coords(fam16.base_params())
    np.testing.assert_array_equal(c[0], g0.x0)
    np.testing.assert_array_equal(c[4], g0.lz)


def test_topology_changing_params_raise():
    pkg = make_2p5d_package(16)
    # independent per-chiplet offsets split the shared cut lines of the
    # grid-aligned placement -> different cut-grid -> clear error
    with pytest.raises(TopologyError, match="topology"):
        PackageFamily(pkg, params=("offsets",))
    with pytest.raises(TopologyError, match="topology"):
        PackageFamily(pkg, params=("offset:chiplet_5",))
    # discrete discretization knobs are rejected up front
    with pytest.raises(TopologyError, match="topology"):
        PackageFamily(pkg, params=("nx",))
    with pytest.raises(ValueError, match="unknown parameter spec"):
        PackageFamily(pkg, params=("warp_factor",))
    with pytest.raises(ValueError, match="unknown layer"):
        PackageFamily(pkg, params=("thickness:nope",))


def test_validate_params_rejects_collisions(fam16):
    lo, hi = fam16.param_bounds().T
    bad = fam16.base_params()
    bad[0] = 4 * hi[0]  # drive column 0 into its neighbor's cut lines
    with pytest.raises(TopologyError, match="fixed-topology region"):
        fam16.validate_params(bad)
    fam16.validate_params(fam16.sample_params(8, seed=0))  # in-box is fine


def test_family_registry_and_baseline_fallback(fam16):
    assert set(available_family_fidelities()) >= {"rc", "dss", "fvm"}
    with pytest.raises(NotImplementedError, match="per-package"):
        build_family(fam16, "hotspot")
    with pytest.raises(KeyError, match="unknown fidelity"):
        build_family(fam16, "nope")


# ---------------------------------------------------------------------------
# batched vs per-candidate loop (Table-6 systems)
# ---------------------------------------------------------------------------
def test_steady_matches_loop_2p5d(fam16):
    params = np.vstack([fam16.base_params(),
                        fam16.sample_params(3, seed=1)])
    q = np.full((4, 16), 3.0)
    with jax.enable_x64(True):
        sim = build_family(fam16, "rc", dtype=jnp.float64)
        th = sim.steady_state_batch(params, q)
        temps = np.asarray(sim.observe_batch(th, params))
        loop = _loop_steady(fam16, params, q, dtype=jnp.float64)
    assert np.abs(temps - loop).max() < 1e-6


def test_steady_matches_loop_3d():
    fam = PackageFamily(make_3d_package(16, tiers=3),
                        params=("grid_offsets",))
    params = np.vstack([fam.base_params(), fam.sample_params(2, seed=2)])
    q = np.full((3, 48), 1.2)
    with jax.enable_x64(True):
        sim = build_family(fam, "rc", dtype=jnp.float64)
        th = sim.steady_state_batch(params, q)
        temps = np.asarray(sim.observe_batch(th, params))
        loop = _loop_steady(fam, params, q, dtype=jnp.float64)
    assert np.abs(temps - loop).max() < 1e-6


def test_steady_degenerate_b1(fam16):
    params = fam16.sample_params(1, seed=3)
    q = np.full((1, 16), 2.5)
    with jax.enable_x64(True):
        sim = build_family(fam16, "rc", dtype=jnp.float64)
        temps = np.asarray(sim.observe_batch(
            sim.steady_state_batch(params, q), params))
        loop = _loop_steady(fam16, params, q, dtype=jnp.float64)
    assert temps.shape == (1, 16)
    assert np.abs(temps - loop).max() < 1e-6


def test_transient_matches_loop(fam16):
    params = fam16.sample_params(2, seed=4)
    T, dt = 25, 0.01
    q = np.full((T, 2, 16), 2.0)
    with jax.enable_x64(True):
        sim = build_family(fam16, "rc", dtype=jnp.float64)
        obs = np.asarray(sim.simulate_family(params, q, dt))
        assert obs.shape == (T, 2, 16)
        for b in range(2):
            m = build(fam16.instantiate(params[b]), "rc",
                      dtype=jnp.float64)
            single = np.asarray(m.make_simulator(dt)(m.zero_state(),
                                                     q[:, b]))
            assert np.abs(obs[:, b] - single).max() < 1e-6


def test_dss_family_matches_loop(fam16):
    params = fam16.sample_params(2, seed=5)
    T = 25
    q = np.full((T, 2, 16), 2.0)
    with jax.enable_x64(True):
        sim = build_family(fam16, "dss", ts=0.01, dtype=jnp.float64)
        obs = np.asarray(sim.simulate_family(params, q))
        for b in range(2):
            m = build(fam16.instantiate(params[b]), "dss", ts=0.01,
                      dtype=jnp.float64)
            single = np.asarray(m.simulate(m.zero_state(), q[:, b]))
            # expm conditioning bounds the match looser than the RC path
            assert np.abs(obs[:, b] - single).max() < 5e-3


def test_fvm_family_matches_loop():
    fam = PackageFamily(make_2p5d_package(4), params=("grid_offsets",))
    params = np.vstack([fam.base_params(), fam.sample_params(1, seed=6)])
    q = np.full((2, 4), 3.0)
    sim = build_family(fam, "fvm")
    th = sim.steady_state_batch(params, q)
    temps = np.asarray(sim.observe_batch(th, params))
    loop = _loop_steady(fam, params, q, fidelity="fvm")
    assert np.abs(temps - loop).max() < 2e-3  # f32 CG tolerance class


# ---------------------------------------------------------------------------
# solver tier (PR 3): matrix-free family transient vs the dense tier
# ---------------------------------------------------------------------------
def test_family_solver_registry(fam16):
    with pytest.raises(NotImplementedError, match="matrix-free"):
        build_family(fam16, "fvm", solver="dense")
    with pytest.raises(ValueError, match="unknown solver"):
        build_family(fam16, "rc", solver="sparse_lu")


def test_cg_family_transient_casts_params(fam16):
    """Regression: the cg-tier family transient must cast params to the
    model dtype inside the trace — an f32 model fed float64 params under
    enable_x64 raised a lax.scan carry-dtype mismatch."""
    with jax.enable_x64(True):
        sim = build_family(fam16, "rc", solver="cg")  # dtype f32
        params = fam16.sample_params(2, seed=12)      # float64 host draw
        q = np.full((5, 2, 16), 2.0)
        obs = np.asarray(sim.simulate_family(params, q, 0.01))
    assert obs.shape == (5, 2, 16) and np.isfinite(obs).all()


def test_transient_cross_solver_family(fam16):
    params = fam16.sample_params(3, seed=7)
    T, dt = 25, 0.01
    q = np.full((T, 3, 16), 2.0)
    with jax.enable_x64(True):
        dense = build_family(fam16, "rc", dtype=jnp.float64,
                             solver="dense")
        cg = build_family(fam16, "rc", dtype=jnp.float64, solver="cg")
        od = np.asarray(dense.simulate_family(params, q, dt))
        oc = np.asarray(cg.simulate_family(params, q, dt))
    assert np.abs(od - oc).max() < 1e-6


def test_steady_degenerate_b1_cg(fam16):
    """B=1 family on the cg tier still reproduces the per-package loop
    — the degenerate case of the solver tier's batched path."""
    params = fam16.sample_params(1, seed=8)
    q = np.full((1, 16), 2.5)
    with jax.enable_x64(True):
        sim = build_family(fam16, "rc", dtype=jnp.float64, solver="cg")
        temps = np.asarray(sim.observe_batch(
            sim.steady_state_batch(params, q), params))
        loop = _loop_steady(fam16, params, q, dtype=jnp.float64,
                            solver="cg")
        loop_dense = _loop_steady(fam16, params, q, dtype=jnp.float64)
    assert np.abs(temps - loop).max() < 1e-6
    assert np.abs(temps - loop_dense).max() < 1e-6


def test_grad_peak_steady_through_numeric_phase():
    """Differentiability regression (PR 5 satellite, groundwork for
    gradient-based DSE): jax.grad of the peak steady temperature w.r.t.
    placement/HTC/thickness family params must flow through the numeric
    phase and match central finite differences — catches any
    accidentally non-differentiable op sneaking into assembly."""
    fam = PackageFamily(make_2p5d_package(16),
                        params=("grid_offsets", "htc_top",
                                "thickness:tim"))
    q = np.full((16,), 3.0)
    with jax.enable_x64(True):
        sim = build_family(fam, "rc", dtype=jnp.float64)
        p0 = jnp.asarray(fam.sample_params(1, seed=11)[0])

        def peak(p):
            return sim.peak_steady(p[None], q[None])[0]

        g = np.asarray(jax.grad(peak)(p0))
        assert g.shape == (fam.n_params,)
        assert np.all(np.isfinite(g))
        # hotter with worse cooling: dT/d(htc_top) < 0, and squeezing the
        # TIM (better conduction to the lid) also cools the peak
        i_htc = fam.param_names.index("htc_top")
        i_tim = fam.param_names.index("thickness:tim")
        assert g[i_htc] < 0 and g[i_tim] > 0
        # central finite differences over every parameter class
        for k in (0, i_htc, i_tim):
            h = max(1e-7 * abs(float(p0[k])), 1e-9)
            fd = (peak(p0.at[k].add(h)) - peak(p0.at[k].add(-h))) / (2 * h)
            assert abs(g[k] - fd) <= 1e-4 * max(abs(fd), 1e-3), \
                (fam.param_names[k], g[k], float(fd))


def test_fvm_family_hoists_static_blocks():
    """FVM throughput fix (PR 5 satellite): blocks that do not move with
    any parameter are rasterized once on the host — scalar-only families
    trace ZERO per-candidate rasterization — while results still match
    the per-candidate voxelize loop."""
    fam = PackageFamily(make_2p5d_package(4),
                        params=("power_scale", "htc_top"))
    sim = build_family(fam, "fvm")
    assert len(sim.blocks) > 0 and len(sim._traced_blocks) == 0
    # no masks -> no select ops in the per-candidate jaxpr at all
    jaxpr = jax.make_jaxpr(sim._fields)(fam.base_params())
    assert not any(e.primitive.name == "select_n" for e in jaxpr.eqns)
    params = np.array([[1.0, fam.template.htc_top],
                       [2.0, 0.5 * fam.template.htc_top]])
    q = np.full((2, 4), 3.0)
    temps = np.asarray(sim.observe_batch(
        sim.steady_state_batch(params, q), params))
    for b in range(2):
        m = build(fam.instantiate(params[b]), "fvm")
        loop = np.asarray(m.observe(m.steady_state(q[b] * params[b, 0])))
        assert np.abs(temps[b] - loop).max() < 2e-3  # f32 CG class
    # placement families keep the movers traced (and keep matching the
    # loop — covered by test_fvm_family_matches_loop)
    moving = build_family(
        PackageFamily(make_2p5d_package(4), params=("grid_offsets",)),
        "fvm")
    assert len(moving._traced_blocks) == len(moving.blocks)


def test_power_scale_and_ambient_params():
    fam = PackageFamily(make_2p5d_package(4),
                        params=("t_ambient", "power_scale"))
    q = np.full((2, 4), 3.0)
    params = np.array([[25.0, 1.0], [35.0, 2.0]])
    sim = build_family(fam, "rc")
    temps = np.asarray(sim.observe_batch(
        sim.steady_state_batch(params, q), params))
    rise0, rise1 = temps[0] - 25.0, temps[1] - 35.0
    # theta is linear in q: doubling power_scale doubles the rise, and
    # t_ambient shifts the observation only
    np.testing.assert_allclose(rise1, 2 * rise0, rtol=1e-4)
