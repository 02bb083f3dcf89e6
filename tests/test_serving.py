"""Thermal-oracle serving subsystem (PR 7): continuous batching,
deadline/overflow/degraded robustness, warm-cache hits, f64 parity.

Regression bars: batched service answers must match direct ``build()`` /
``build_family()`` references to <=1e-6 degC in f64 over every request
kind; a repeat geometry must hit the model cache (no second build);
deadline expiry, queue overflow and a CG iteration cap must come back as
structured responses — and the service must keep answering afterwards.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dtpm import ThermalManager
from repro.core.family import PackageFamily
from repro.core.fidelity import build, build_family
from repro.core.geometry import make_2p5d_package
from repro.serving import ModelCache, ThermalOracle

ROM_OPTS = {"n_moments": 2, "ts": 0.01}
DT = 0.01


def _pkg():
    return make_2p5d_package(4)


# ---------------------------------------------------------------------------
# warm cache: repeat geometries skip the one-time build
# ---------------------------------------------------------------------------
def test_repeat_geometry_hits_cache():
    with ThermalOracle(fidelity="rom", capacity=2,
                       build_opts=ROM_OPTS) as oracle:
        q = np.full(4, 3.0)
        first = oracle.query_steady(_pkg(), q)
        # an INDEPENDENTLY constructed, structurally identical package
        second = oracle.query_steady(_pkg(), q)
        assert first.status == "ok" and second.status == "ok"
        assert first.cache_hit is False and second.cache_hit is True
        stats = oracle.cache.stats()
        assert stats["misses"] == 1 and stats["hits"] >= 1
        np.testing.assert_allclose(second.value, first.value)
        # warm() on a third copy is a pure hit
        _, hit, _ = oracle.warm(_pkg())
        assert hit is True


def test_warm_prebuilds_before_traffic():
    with ThermalOracle(fidelity="rom", capacity=2,
                       build_opts=ROM_OPTS) as oracle:
        key, hit, build_s = oracle.warm(_pkg())
        assert hit is False and build_s > 0
        r = oracle.query_steady(_pkg(), np.full(4, 3.0))
        assert r.status == "ok" and r.cache_hit is True


# ---------------------------------------------------------------------------
# f64 parity: batched serving answers == direct build()/build_family()
# ---------------------------------------------------------------------------
def test_f64_parity_all_request_kinds():
    pkg = _pkg()
    fam = PackageFamily(_pkg(), params=("htc_top", "power_scale"))
    opts = {**ROM_OPTS, "dtype": jnp.float64}
    rng = np.random.default_rng(0)
    q = rng.uniform(1.0, 4.0, 4)
    q_traj = rng.uniform(0.5, 3.0, (25, 4))
    powers = rng.uniform(2.0, 9.0, (50, 4))
    params = fam.sample_params(2, seed=3)

    with ThermalOracle(fidelity="rom", capacity=3, x64=True,
                       build_opts=opts) as oracle:
        r_steady = oracle.submit_steady(pkg, q)
        r_tran = oracle.submit_transient(pkg, q_traj, DT)
        r_dtpm = oracle.submit_dtpm(pkg, powers)
        r_fs = [oracle.submit_family_steady(fam, p, q) for p in params]
        r_ft = [oracle.submit_family_transient(fam, p, q_traj, DT)
                for p in params]
        responses = [p.result(timeout=300) for p in
                     [r_steady, r_tran, r_dtpm] + r_fs + r_ft]
    assert [r.status for r in responses] == ["ok"] * len(responses)

    with jax.enable_x64(True):
        m = build(pkg, "rom", **opts)
        ref_steady = np.asarray(m.observe(m.steady_state(q)))
        ref_tran = np.asarray(m.make_simulator(DT)(m.zero_state(),
                                                   q_traj))
        mgr = ThermalManager(dss=m)
        ref_state, ref_tmax, _ = mgr.run(powers)
        ref_tmax = np.asarray(ref_tmax)
        ref_violations = int(ref_state.violations)
        sim = build_family(fam, "rom", **opts)
        ref_fs = np.asarray(sim.observe_batch(
            sim.steady_state_batch(params, np.tile(q, (2, 1))), params))
        ref_ft = np.asarray(sim.simulate_family(
            params, np.tile(q_traj[:, None, :], (1, 2, 1)), DT))

    steady, tran, dtpm = responses[0], responses[1], responses[2]
    assert np.abs(steady.value - ref_steady).max() < 1e-6
    assert np.abs(tran.value - ref_tran).max() < 1e-6
    assert np.abs(dtpm.value - ref_tmax).max() < 1e-6
    assert dtpm.info["violations"] == ref_violations
    for b in range(2):
        assert np.abs(responses[3 + b].value - ref_fs[b]).max() < 1e-6
        assert np.abs(responses[5 + b].value - ref_ft[:, b]).max() < 1e-6


# ---------------------------------------------------------------------------
# continuous batching mechanics
# ---------------------------------------------------------------------------
def test_queued_same_shape_requests_coalesce():
    oracle = ThermalOracle(fidelity="rom", capacity=3,
                           build_opts=ROM_OPTS, autostart=False)
    try:
        q_traj = np.full((20, 4), 2.0)
        pends = [oracle.submit_transient(_pkg(), q_traj, DT)
                 for _ in range(3)]
        oracle.start()          # whole queue visible at first collect
        rs = [p.result(timeout=300) for p in pends]
        assert [r.status for r in rs] == ["ok"] * 3
        assert all(r.occupancy == 1.0 for r in rs)   # one full batch
        # padded slots are invisible: identical inputs, identical answers
        np.testing.assert_allclose(rs[1].value, rs[0].value)
        np.testing.assert_allclose(rs[2].value, rs[0].value)
    finally:
        oracle.close()


def test_mixed_kind_requests_group_separately():
    oracle = ThermalOracle(fidelity="rom", capacity=4,
                           build_opts=ROM_OPTS, autostart=False)
    try:
        q = np.full(4, 3.0)
        q_traj = np.full((20, 4), 2.0)
        pends = [oracle.submit_steady(_pkg(), q),
                 oracle.submit_transient(_pkg(), q_traj, DT),
                 oracle.submit_steady(_pkg(), q),
                 oracle.submit_transient(_pkg(), np.full((30, 4), 2.0),
                                         DT)]
        oracle.start()
        rs = [p.result(timeout=300) for p in pends]
        assert [r.status for r in rs] == ["ok"] * 4
        assert rs[1].value.shape == (20, 4)
        assert rs[3].value.shape == (30, 4)   # different T, own group
        np.testing.assert_allclose(rs[2].value, rs[0].value)
    finally:
        oracle.close()


# ---------------------------------------------------------------------------
# robustness: structured failure responses, service stays live
# ---------------------------------------------------------------------------
def test_deadline_expiry_is_structured_and_service_survives():
    oracle = ThermalOracle(fidelity="rom", capacity=2,
                           build_opts=ROM_OPTS, autostart=False)
    try:
        q = np.full(4, 3.0)
        doomed = oracle.submit_steady(_pkg(), q, deadline_s=-0.001)
        oracle.start()
        r = doomed.result(timeout=60)
        assert r.status == "timeout" and not r.ok
        assert "deadline" in r.detail
        # the service answers the next request normally
        live = oracle.query_steady(_pkg(), q)
        assert live.status == "ok"
        snap = oracle.telemetry.snapshot()
        assert snap["by_status"]["timeout"] == 1
    finally:
        oracle.close()


def test_queue_overflow_is_structured_and_service_survives():
    oracle = ThermalOracle(fidelity="rom", capacity=2, max_queue=1,
                           build_opts=ROM_OPTS, autostart=False)
    try:
        q = np.full(4, 3.0)
        kept = oracle.submit_steady(_pkg(), q)
        spilled = oracle.submit_steady(_pkg(), q)
        assert spilled.done()              # rejected synchronously
        r = spilled.result(timeout=1)
        assert r.status == "overflow" and not r.ok
        assert "queue full" in r.detail
        oracle.start()
        assert kept.result(timeout=300).status == "ok"
        assert oracle.telemetry.snapshot()["by_status"]["overflow"] == 1
    finally:
        oracle.close()


def test_cg_iteration_cap_degrades_response_and_service_survives():
    import warnings
    capped = {"solver": "cg", "cg_maxiter": 2, "refine_passes": 0}
    with ThermalOracle(fidelity="rc", capacity=2,
                       build_opts=capped) as oracle:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            r = oracle.query_steady(_pkg(), np.full(4, 3.0))
        assert r.status == "degraded" and r.ok    # answered, flagged
        assert r.cg is not None and r.cg["converged"] is False
        assert "iteration cap" in r.detail
        # same service, solvable config: healthy again
        good = oracle.query_steady(_pkg(), np.full(4, 3.0),
                                   opts={"solver": "dense"})
        assert good.status == "ok" and good.cg is None
        snap = oracle.telemetry.snapshot()
        assert snap["by_status"]["degraded"] == 1
        assert any(snap["cg_unconverged_sites"].values())


def test_solver_exception_is_structured_and_service_survives():
    with ThermalOracle(fidelity="rom", capacity=2,
                       build_opts=ROM_OPTS) as oracle:
        bad = oracle.submit_steady(_pkg(), np.full(7, 3.0))  # wrong S
        r = bad.result(timeout=300)
        assert r.status == "error" and not r.ok and r.detail
        live = oracle.query_steady(_pkg(), np.full(4, 3.0))
        assert live.status == "ok"


def test_client_side_result_timeout_raises():
    oracle = ThermalOracle(fidelity="rom", capacity=2,
                           build_opts=ROM_OPTS, autostart=False)
    try:
        pend = oracle.submit_steady(_pkg(), np.full(4, 3.0))
        with pytest.raises(TimeoutError):
            pend.result(timeout=0.05)      # worker never started
    finally:
        oracle.close()


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------
def test_telemetry_snapshot_shape_and_rejected_oversized_counters():
    # a 1-byte budget makes EVERY model oversized: the cache must refuse
    # to retain them (rejected counter) rather than pinning one entry
    # forever while evicting the rest — the service still answers every
    # query from the handed-off in-flight build
    cache = ModelCache(max_bytes=1)
    with ThermalOracle(fidelity="rom", capacity=2, cache=cache,
                       build_opts=ROM_OPTS) as oracle:
        q = np.full(4, 3.0)
        oracle.query_steady(make_2p5d_package(4), q)
        oracle.query_steady(make_2p5d_package(4, htc_top=9000.0), q)
        snap = oracle.telemetry.snapshot()
    assert snap["submitted"] == 2 and snap["completed"] == 2
    assert snap["by_status"] == {"ok": 2}
    lat = snap["latency"]["steady"]
    assert lat["n"] == 2 and 0 < lat["p50_s"] <= lat["p99_s"]
    assert 0 < snap["mean_batch_occupancy"] <= 1.0
    assert snap["cache"]["entries"] == 0   # nothing oversized retained
    assert snap["cache"]["rejected"] >= 2
    assert snap["cache"]["bytes"] == 0         # accounting stays exact
    assert isinstance(snap["cg_unconverged_sites"], dict)
