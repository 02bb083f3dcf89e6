"""Hypothesis property tests on system invariants (deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the 'dev' extra")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (ThermalRCModel, build_network, discretize_rc,
                        krylov_basis, make_2p5d_package, make_3d_package,
                        project_network, spectral_radius)
from repro.kernels.flash_attn.ref import gqa_ref
from repro.models.layers import apply_rope


@st.composite
def package_cfg(draw):
    n_side = draw(st.sampled_from([1, 2]))
    htc = draw(st.floats(500.0, 8000.0))
    return n_side * n_side, htc


@st.composite
def packages(draw):
    """Random VALID Package geometries across the generator space:
    2.5D/3D, chiplet count, cooling, funnel nodes, ambient."""
    kind = draw(st.sampled_from(["2p5d", "3d"]))
    n_side = draw(st.sampled_from([1, 2, 3]))
    htc = draw(st.floats(500.0, 20000.0))
    t_amb = draw(st.floats(15.0, 45.0))
    funnel = draw(st.booleans())
    if kind == "3d":
        tiers = draw(st.sampled_from([2, 3]))
        return make_3d_package(n_side * n_side, tiers=tiers, htc_top=htc,
                               t_ambient=t_amb, funnel=funnel)
    return make_2p5d_package(n_side * n_side, htc_top=htc,
                             t_ambient=t_amb, funnel=funnel)


@given(package_cfg())
def test_rc_network_invariants(cfg):
    n_chip, htc = cfg
    pkg = make_2p5d_package(n_chip, htc_top=htc)
    net = build_network(pkg)
    g = net.g_dense()
    # symmetry of conductances
    np.testing.assert_allclose(g, g.T, rtol=1e-9)
    # diagonal dominance with convection grounding: row sums <= 0
    assert np.all(g.sum(axis=1) <= 1e-9)
    # positive capacitances
    assert np.all(net.C > 0)
    # power matrix: columns sum to 1 (all power lands somewhere)
    np.testing.assert_allclose(net.P.sum(axis=0), 1.0, rtol=1e-9)


@given(packages())
@settings(max_examples=10, deadline=None)
def test_neg_g_spd_after_assembly(pkg):
    """-G of any generated geometry stays symmetric positive definite —
    the property both the dense Cholesky tier and the CG tier rest on."""
    net = build_network(pkg)
    neg_g = -net.g_dense()
    np.testing.assert_allclose(neg_g, neg_g.T, rtol=1e-9)
    np.linalg.cholesky(neg_g)  # raises LinAlgError unless SPD


@given(packages(), st.integers(1, 3))
@settings(max_examples=8, deadline=None)
def test_rom_reduced_g_stays_spd(pkg, n_moments):
    """The Krylov congruence projection preserves definiteness on any
    generated geometry: -Ghat = -V' G V stays SPD and Chat = V' C V stays
    the identity (C-orthonormal basis) — the PRIMA stability property the
    ROM rung's prefactored steady solve and ZOH rest on."""
    net = build_network(pkg)
    v = krylov_basis(net, n_moments=n_moments)
    ghat, chat, _, _ = project_network(net, v)
    np.testing.assert_allclose(ghat, ghat.T, rtol=1e-9)
    np.linalg.cholesky(-ghat)  # raises LinAlgError unless SPD
    np.testing.assert_allclose(chat, np.eye(v.shape[1]), atol=1e-9)


@given(packages(), st.floats(0.3, 4.0))
@settings(max_examples=8, deadline=None)
def test_cg_solver_matches_dense_steady(pkg, p_chip):
    """The matrix-free CG tier reproduces the dense steady state to
    <=1e-6 degC on random valid geometries (f64)."""
    with jax.enable_x64(True):
        net = build_network(pkg)
        dense = ThermalRCModel(net, dtype=jnp.float64, solver="dense")
        cg = ThermalRCModel(net, dtype=jnp.float64, solver="cg")
        q = np.full(len(dense.source_names), p_chip)
        t_dense = np.asarray(dense.observe(dense.steady_state(q)))
        t_cg = np.asarray(cg.observe(cg.steady_state(q)))
    assert np.abs(t_dense - t_cg).max() < 1e-6


@given(st.floats(0.2, 3.0), st.floats(0.001, 0.1))
def test_steady_state_physicality(p_chip, ts):
    pkg = make_2p5d_package(4)
    rc = ThermalRCModel(build_network(pkg))
    theta = np.asarray(rc.steady_state(np.full(4, p_chip)))
    # above ambient everywhere; hotter with more power (monotonicity)
    assert np.all(theta > -1e-4)
    theta2 = np.asarray(rc.steady_state(np.full(4, p_chip * 1.5)))
    assert np.all(theta2 >= theta - 1e-4)
    # DSS stability at any sampling period
    assert spectral_radius(discretize_rc(rc, ts=ts)) < 1.0


@given(st.integers(0, 6), st.integers(2, 5))
@settings(max_examples=8)
def test_attention_causality(perturb_pos, lq):
    """Output at position i must not depend on tokens after i."""
    rng = np.random.default_rng(0)
    l = 8
    q = jnp.asarray(rng.normal(size=(1, 2, l, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, l, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, l, 16)), jnp.float32)
    out1 = gqa_ref(q, k, v, causal=True)
    k2 = k.at[:, :, perturb_pos + 1:].add(7.0)
    v2 = v.at[:, :, perturb_pos + 1:].add(-3.0)
    out2 = gqa_ref(q, k2, v2, causal=True)
    np.testing.assert_allclose(out1[:, :, :perturb_pos + 1],
                               out2[:, :, :perturb_pos + 1], atol=1e-5)


@given(st.integers(0, 1000))
@settings(max_examples=10)
def test_rope_preserves_norm(pos):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(1, 1, 2, 32)), jnp.float32)
    r = apply_rope(x, jnp.array([[pos]]), theta=10000.0)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(x)),
                               np.linalg.norm(np.asarray(r)), rtol=1e-5)
