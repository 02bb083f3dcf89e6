"""ROM fidelity rung (PR 4): Krylov moment-matching projection.

Regression bars: the reduced model must track the full-order DSS to
<=0.1 degC (steady AND transient, default accuracy knob) on every
Table-6 system, the family path must reproduce the per-package ROM loop
to <=1e-5 degC over a shared basis, and accuracy must improve
monotonically with the basis dimension r.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (PackageFamily, ThermalSimulator, build,
                        build_family, krylov_basis, make_2p5d_package,
                        package_from_name)
from repro.core.rc_model import _resolve_cap_multipliers, build_network
from repro.core.workloads import wl1

DT = 0.01


# ---------------------------------------------------------------------------
# ROM vs full-order DSS on the Table-6 systems (default accuracy knob)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("system", ["2p5d_16", "2p5d_36", "2p5d_64",
                                    "3d_16x3"])
def test_rom_tracks_dss_table6(system):
    pkg, s = package_from_name(system)
    rom = build(pkg, "rom", ts=DT)
    dss = build(pkg, "dss", ts=DT)
    assert rom.r < rom.n_full  # it actually reduces
    # steady state
    q = np.full(s, 3.0)
    t_rom = np.asarray(rom.observe(rom.steady_state(q)))
    t_dss = np.asarray(dss.observe(dss.steady_state(q)))
    assert np.abs(t_rom - t_dss).max() < 0.1, system
    # transient on the Table-6 WL1 trace
    q_traj = wl1(s, dt=DT)[:300].astype(np.float32)
    o_rom = np.asarray(rom.make_simulator(DT)(rom.zero_state(), q_traj))
    o_dss = np.asarray(dss.make_simulator(DT)(dss.zero_state(), q_traj))
    assert np.abs(o_rom - o_dss).max() < 0.1, system


def test_rom_protocol_and_dss_surface():
    pkg = make_2p5d_package(4)
    rom = build(pkg, "rom", ts=DT)
    assert isinstance(rom, ThermalSimulator)
    assert rom.fidelity == "rom"
    assert rom.n == rom.r and rom.reduction_ratio > 1.0
    # the DSS-consumer surface (ThermalManager contract)
    assert rom.ad.shape == (rom.r, rom.r)
    assert rom.bd.shape == (rom.r, len(rom.source_names))
    assert rom.H.shape == (len(rom.tags), rom.r)
    # batched rollout at a regenerated dt matches the single trace
    q = np.full((30, 4), 2.0, np.float32)
    single = np.asarray(rom.make_simulator(DT / 2)(rom.zero_state(), q))
    batch = np.asarray(rom.simulate_batch(
        rom.zero_state(batch=3), np.tile(q[:, None, :], (1, 3, 1)),
        DT / 2))
    assert batch.shape == (30, 3, 4)
    for b in range(3):
        np.testing.assert_allclose(batch[:, b], single, atol=1e-4)
    # expand() lifts the reduced steady state back to N nodes
    th_full = rom.expand(rom.steady_state(np.full(4, 3.0)))
    assert th_full.shape == (rom.n_full,)
    assert th_full.max() > 10  # heat actually flows


def test_rom_zoh_cache_lru_bounded_and_bitwise_stable():
    """The per-dt (ad, bd) regeneration cache must stay bounded when a
    DTPM controller sweeps sampling periods (cap = _ZOH_CACHE_CAP,
    mirroring the executor's dt-keyed jit-cache bound), behave as true
    LRU (hits refresh recency), and regenerate evicted entries
    bitwise-identically."""
    pkg = make_2p5d_package(4)
    rom = build(pkg, "rom", ts=DT)
    cap = rom._ZOH_CACHE_CAP
    dts = [DT * (1 + k) for k in range(cap + 5)]   # > cap distinct dts
    first = {dt: tuple(np.asarray(m).copy() for m in rom._zoh(dt))
             for dt in dts}
    assert len(rom._zoh_cache) == cap
    # second sweep: every entry regenerates (or hits) bitwise-stable
    for dt in dts:
        ad, bd = rom._zoh(dt)
        assert np.array_equal(np.asarray(ad), first[dt][0])
        assert np.array_equal(np.asarray(bd), first[dt][1])
    assert len(rom._zoh_cache) == cap
    # true LRU, not FIFO: a hot key re-hit between insertions survives
    # a sweep that evicts everything older
    hot = dts[-cap]                      # currently the LRU-front entry
    rom._zoh(hot)                        # refresh recency
    for k in range(cap - 1):             # fill all but one slot
        rom._zoh(DT * 100 * (k + 1))
    assert round(float(hot), 12) in rom._zoh_cache
    assert len(rom._zoh_cache) == cap


def test_rom_basis_injection_and_validation():
    pkg = make_2p5d_package(4)
    net = build_network(pkg,
                        cap_multipliers=_resolve_cap_multipliers(pkg, None))
    v = krylov_basis(net, n_moments=2)
    rom = build(pkg, "rom", basis=v)
    assert rom.r == v.shape[1]
    # C-orthonormality of the Krylov basis: V' C V = I
    np.testing.assert_allclose(v.T @ (net.C[:, None] * v),
                               np.eye(v.shape[1]), atol=1e-10)
    with pytest.raises(ValueError, match="basis"):
        build(pkg, "rom", basis=v[:-1])
    # explicit r truncates to exactly r dominant columns
    rom_r = build(pkg, "rom", r=10)
    assert rom_r.r == 10


def test_rational_multipoint_cuts_r_below_6s_at_equal_certified_error():
    """The rational multi-point knob's reason to exist: front-loading
    moments at DC plus one dominance-truncated block at a shift near the
    fast end of the spectrum certifies TIGHTER transient error than the
    default single-point 6S basis, with fewer columns (r=84 < 96 here).
    Certificates come from the router's residual-based bound, so the
    comparison is a-posteriori rigorous, not eyeballed."""
    from repro.core.dss import zoh_discretize
    from repro.core.router import ErrorCertifier
    pkg, s = package_from_name("2p5d_16")
    net = build_network(pkg,
                        cap_multipliers=_resolve_cap_multipliers(pkg, None))
    certifier = ErrorCertifier(net)
    q_traj = wl1(s, dt=DT)[:80].astype(np.float64)

    def certified(v):
        rom = build(pkg, "rom", basis=v, ts=DT)
        ad, bd = zoh_discretize(rom._a, rom._b, DT)
        th = np.zeros((q_traj.shape[0] + 1, rom.r))
        for k in range(q_traj.shape[0]):
            th[k + 1] = ad @ th[k] + bd @ q_traj[k]
        return certifier.certify_rom_transient(rom, th, q_traj, DT)

    v_std = krylov_basis(net, n_moments=6)
    v_rat = krylov_basis(net, r=84, n_moments=(5, 1), shifts=(0.0, 100.0))
    assert v_std.shape[1] == 6 * s
    assert v_rat.shape[1] == 84 < v_std.shape[1]
    # the shared-basis orthogonalization holds across expansion points
    np.testing.assert_allclose(v_rat.T @ (net.C[:, None] * v_rat),
                               np.eye(v_rat.shape[1]), atol=1e-10)
    assert certified(v_rat) < certified(v_std)
    # knob validation: per-shift moment counts must match the shifts
    with pytest.raises(ValueError, match="n_moments"):
        krylov_basis(net, n_moments=(5, 1, 1), shifts=(0.0, 100.0))


def test_rom_error_monotone_in_r():
    """r-sweep smoke test: more basis columns, weakly smaller error."""
    pkg = make_2p5d_package(16)
    dss = build(pkg, "dss", ts=DT)
    q_traj = wl1(16, dt=DT)[:300].astype(np.float32)
    ref = np.asarray(dss.make_simulator(DT)(dss.zero_state(), q_traj))
    errs = []
    for moments in (2, 4, 6):
        rom = build(pkg, "rom", n_moments=moments, ts=DT)
        obs = np.asarray(rom.make_simulator(DT)(rom.zero_state(), q_traj))
        errs.append(np.abs(obs - ref).max())
    # strict ordering with slack for solver noise (measured: each extra
    # pair of moments cuts the error by >5x)
    assert errs[1] < errs[0] * 1.05 and errs[2] < errs[1] * 1.05, errs


# ---------------------------------------------------------------------------
# family path: one template basis, batched reduced assembly
# ---------------------------------------------------------------------------
def test_rom_family_matches_loop():
    fam = PackageFamily(make_2p5d_package(16),
                        params=("grid_offsets", "htc_top"))
    params = np.vstack([fam.base_params(), fam.sample_params(2, seed=1)])
    q = np.full((3, 16), 3.0)
    t_steps = 25
    q_traj = np.full((t_steps, 3, 16), 2.0)
    with jax.enable_x64(True):
        sim = build_family(fam, "rom", ts=DT, dtype=jnp.float64)
        temps = np.asarray(sim.observe_batch(
            sim.steady_state_batch(params, q), params))
        obs = np.asarray(sim.simulate_family(params, q_traj))
        assert obs.shape == (t_steps, 3, 16)
        for b in range(3):
            m = build(fam.instantiate(params[b]), "rom", ts=DT,
                      dtype=jnp.float64, basis=sim.V)
            loop_s = np.asarray(m.observe(m.steady_state(q[b])))
            loop_t = np.asarray(m.make_simulator(DT)(m.zero_state(),
                                                     q_traj[:, b]))
            assert np.abs(temps[b] - loop_s).max() < 1e-5, b
            assert np.abs(obs[:, b] - loop_t).max() < 1e-5, b


def test_rom_family_power_scale_and_ambient():
    fam = PackageFamily(make_2p5d_package(4),
                        params=("t_ambient", "power_scale"))
    q = np.full((2, 4), 3.0)
    params = np.array([[25.0, 1.0], [35.0, 2.0]])
    sim = build_family(fam, "rom")
    temps = np.asarray(sim.observe_batch(
        sim.steady_state_batch(params, q), params))
    rise0, rise1 = temps[0] - 25.0, temps[1] - 35.0
    # theta_hat is linear in q: doubling power_scale doubles the rise,
    # t_ambient shifts the observation only
    np.testing.assert_allclose(rise1, 2 * rise0, rtol=1e-4)
