"""Sharded family execution layer (PR 5): FamilyExecutor semantics
(padding, chunk streaming, warm-started carry) plus mesh-sharded parity
with the single-device vmap path for the rc/dss/rom family rungs.

The mesh tests need >=8 devices; CI runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (they skip on a
plain single-device session). ``test_sharded_families_subprocess`` keeps
the 8-device acceptance check in tier-1 regardless, by spawning a fresh
interpreter with the flag set.
"""
import contextlib
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import PackageFamily, build, build_family, \
    make_2p5d_package
from repro.distribution import family_exec as exec_mod
from repro.distribution.family_exec import FamilyExecutor

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def fam():
    return PackageFamily(make_2p5d_package(16),
                         params=("grid_offsets", "htc_top"))


# ---------------------------------------------------------------------------
# executor construction / validation
# ---------------------------------------------------------------------------
def test_executor_validation():
    ex = FamilyExecutor()
    assert ex.n_shards == 1 and ex.describe()["devices"] == 1
    with pytest.raises(ValueError, match="devices"):
        FamilyExecutor(mesh=10 ** 6)
    with pytest.raises(ValueError, match="chunk_size"):
        FamilyExecutor(chunk_size=0)
    if len(jax.devices()) >= 8:
        with pytest.raises(ValueError, match="multiple of"):
            FamilyExecutor(mesh=8, chunk_size=12)
        assert FamilyExecutor(mesh=8, chunk_size=16).describe() == \
            {"devices": 8, "chunk_size": 16, "batch_axis": "data"}


def test_shared_executor_namespaces_peer_models(fam):
    """Two peer models sharing one executor must not serve each other's
    compiled closures: each model registers its own jit-cache namespace.
    Regression: a second family over a DIFFERENT package answered with
    the first family's temperatures when keys collided."""
    ex = FamilyExecutor()
    with jax.enable_x64(True):
        a = build_family(fam, "rc", dtype=jnp.float64, executor=ex)
        fam4 = PackageFamily(make_2p5d_package(4),
                             params=("grid_offsets",))
        b = build_family(fam4, "rc", dtype=jnp.float64, executor=ex)
        assert a._ns != b._ns
        qa = np.full((2, 16), 3.0)
        qb = np.full((2, 4), 3.0)
        pa = fam.sample_params(2, seed=1)
        pb = fam4.sample_params(2, seed=1)
        ta = np.asarray(a.observe_batch(a.steady_state_batch(pa, qa), pa))
        tb = np.asarray(b.observe_batch(b.steady_state_batch(pb, qb), pb))
        assert ta.shape == (2, 16) and tb.shape == (2, 4)
        ref = build_family(fam4, "rc", dtype=jnp.float64)
        tb_ref = np.asarray(ref.observe_batch(
            ref.steady_state_batch(pb, qb), pb))
    assert np.abs(tb - tb_ref).max() < 1e-9


def test_executor_batch_plan():
    ex = FamilyExecutor(chunk_size=4)
    assert ex._plan_batch(3) == (3, 3)      # under the chunk: one call
    assert ex._plan_batch(7) == (8, 4)      # padded to chunk multiple
    assert ex._plan_batch(8) == (8, 4)


# ---------------------------------------------------------------------------
# chunk streaming (single device)
# ---------------------------------------------------------------------------
def test_chunked_steady_matches_unchunked(fam):
    """B=7 over chunk_size=2 (pad to 8, 4 chunks, CG warm-started across
    chunks) must match the one-call path and the per-package loop."""
    params = np.vstack([fam.base_params(), fam.sample_params(6, seed=1)])
    q = np.full((7, 16), 3.0)
    with jax.enable_x64(True):
        one_call = build_family(fam, "rc", dtype=jnp.float64)
        chunked = build_family(fam, "rc", dtype=jnp.float64, chunk_size=2)
        t_ref = np.asarray(one_call.observe_batch(
            one_call.steady_state_batch(params, q), params))
        th = chunked.steady_state_batch(params, q)
        # a streamed output that fits the device stays there, joined
        assert isinstance(th, jax.Array) and th.shape == (7, 564)
        t_chunk = np.asarray(chunked.observe_batch(th, params))
        m = build(fam.instantiate(params[3]), "rc", dtype=jnp.float64)
        t_loop = np.asarray(m.observe(m.steady_state(q[3])))
    assert np.abs(t_chunk - t_ref).max() < 1e-6
    assert np.abs(t_chunk[3] - t_loop).max() < 1e-6


def test_chunked_transient_matches_unchunked(fam):
    params = fam.sample_params(5, seed=2)
    T, dt = 12, 0.01
    q = np.full((T, 5, 16), 2.0)
    with jax.enable_x64(True):
        one_call = build_family(fam, "rc", dtype=jnp.float64)
        chunked = build_family(fam, "rc", dtype=jnp.float64, chunk_size=2)
        o_ref = np.asarray(one_call.simulate_family(params, q, dt))
        o_chunk = chunked.simulate_family(params, q, dt)
        assert isinstance(o_chunk, jax.Array)   # fits: joined on device
        assert o_chunk.shape == (T, 5, 16)
    assert np.abs(np.asarray(o_chunk) - o_ref).max() < 1e-6


def test_resident_and_landed_streams_are_bit_identical(fam, monkeypatch):
    """The fit rule moves where a streamed output lives, not what it is:
    B=7 over chunk_size=2 (a pad row in the last chunk) gives the same
    bits for the steady states, their CG stats and the observation
    whether the output stays on the device or lands chunk by chunk."""
    params = np.vstack([fam.base_params(), fam.sample_params(6, seed=8)])
    q = np.full((7, 16), 3.0)
    sim = build_family(fam, "rc", chunk_size=2)
    got = {}
    for name, budget in (("resident", 1 << 40), ("landed", 8)):
        monkeypatch.setattr(FamilyExecutor, "_budget",
                            lambda self, budget=budget: budget)
        th = sim.steady_state_batch(params, q)
        stats = sim.last_cg_stats
        temps = sim.observe_batch(th, params)
        kind = jax.Array if name == "resident" else np.ndarray
        assert isinstance(th, kind) and isinstance(temps, kind), name
        got[name] = [np.asarray(a) for a in
                     (th, temps, stats.iterations, stats.residual,
                      stats.converged)]
    for a, b in zip(got["resident"], got["landed"]):
        np.testing.assert_array_equal(a, b)
    assert got["resident"][0].shape == (7, 564)


def test_over_budget_stream_lands_per_chunk(monkeypatch):
    """The fit rule's threshold: a padded output of exactly the budget's
    bytes per device stays on the device; one byte over the budget and
    it lands on the host, chunk by chunk, and returns numpy."""
    ex = FamilyExecutor(chunk_size=2)
    x = np.arange(21, dtype=np.float32).reshape(7, 3)

    def fn(x):
        return x * 2, x.sum(axis=1)

    padded_bytes = 8 * 3 * 4 + 8 * 4      # B padded 7 -> 8, both leaves
    spans = []

    def record(name, **args):
        spans.append((name, args))
        return contextlib.nullcontext()

    monkeypatch.setattr(exec_mod, "span", record)
    for budget, kind, resident in ((padded_bytes, jax.Array, 1),
                                   (padded_bytes - 1, np.ndarray, 0)):
        monkeypatch.setattr(FamilyExecutor, "_budget",
                            lambda self, budget=budget: budget)
        spans.clear()
        y, s = ex.run("f", fn, (x,), in_axes=(0,))
        assert isinstance(y, kind) and isinstance(s, kind), budget
        assert spans[0] == ("exec.run", {"chunks": 4,
                                         "resident": resident})
        assert [n for n, _ in spans].count("exec.land") == 4 * (1 - resident)
        np.testing.assert_array_equal(np.asarray(y), x * 2)
        np.testing.assert_array_equal(np.asarray(s), x.sum(axis=1))


def test_executor_pad_rows_are_template_candidates(fam):
    """Padding must evaluate VALID geometry: an all-zero pad row would
    put every chiplet at the template spot but htc_top=0 (singular
    convection); the executor pads with base_params() instead, so a
    non-divisible B cannot poison the batch."""
    sim = build_family(fam, "rc", chunk_size=4)
    row = sim._pad_param_row
    np.testing.assert_array_equal(row, fam.base_params())
    assert row[-1] == fam.template.htc_top  # htc slot keeps template value
    params = fam.sample_params(5, seed=3)   # pads 5 -> 8
    q = np.full((5, 16), 3.0)
    temps = np.asarray(sim.observe_batch(
        sim.steady_state_batch(params, q), params))
    assert temps.shape == (5, 16) and np.isfinite(temps).all()


# ---------------------------------------------------------------------------
# mesh sharding (8 simulated host devices)
# ---------------------------------------------------------------------------
@multi_device
def test_mesh_steady_matches_vmap_nondivisible(fam):
    """Acceptance: sharded steady == single-device vmap to <=1e-6 degC in
    f64, including non-divisible B via padding."""
    params = np.vstack([fam.base_params(), fam.sample_params(10, seed=4)])
    q = np.full((11, 16), 3.0)
    with jax.enable_x64(True):
        ref = build_family(fam, "rc", dtype=jnp.float64)
        t_ref = np.asarray(ref.observe_batch(
            ref.steady_state_batch(params, q), params))
        for ndev in (2, 8):
            sim = build_family(fam, "rc", dtype=jnp.float64, mesh=ndev)
            assert sim.exec.n_shards == ndev
            t = np.asarray(sim.observe_batch(
                sim.steady_state_batch(params, q), params))
            assert np.abs(t - t_ref).max() < 1e-6, ndev


@multi_device
def test_mesh_transients_match_vmap_rc_dss_rom(fam):
    params = np.vstack([fam.base_params(), fam.sample_params(6, seed=5)])
    T = 10
    q = np.full((T, 7, 16), 2.0)
    with jax.enable_x64(True):
        for fid, opts in (("rc", {}), ("rc", {"solver": "cg"}),
                          ("dss", {"ts": 0.01}), ("rom", {"ts": 0.01})):
            ref = build_family(fam, fid, dtype=jnp.float64, **opts)
            sim = build_family(fam, fid, dtype=jnp.float64, mesh=8,
                               **opts)
            o_ref = np.asarray(ref.simulate_family(params, q, 0.01))
            o = np.asarray(sim.simulate_family(params, q, 0.01))
            assert np.abs(o - o_ref).max() < 1e-6, (fid, opts)


@multi_device
def test_mesh_rom_steady_matches_vmap(fam):
    params = fam.sample_params(9, seed=6)
    q = np.full((9, 16), 3.0)
    with jax.enable_x64(True):
        ref = build_family(fam, "rom", dtype=jnp.float64)
        sim = build_family(fam, "rom", dtype=jnp.float64, mesh=8)
        t_ref = np.asarray(ref.observe_batch(
            ref.steady_state_batch(params, q), params))
        t = np.asarray(sim.observe_batch(
            sim.steady_state_batch(params, q), params))
    assert np.abs(t - t_ref).max() < 1e-6


@multi_device
def test_mesh_composes_with_chunk_streaming(fam):
    """chunk_size rides on top of the mesh: every chunk splits over the
    shards (per-shard coo_matvec plans, no cross-device edges) and the
    joined stream stays on the devices, batch-sharded, equal to the
    one-call path."""
    params = fam.sample_params(40, seed=7)
    q = np.full((40, 16), 3.0)
    with jax.enable_x64(True):
        ref = build_family(fam, "rc", dtype=jnp.float64)
        sim = build_family(fam, "rc", dtype=jnp.float64, mesh=8,
                           chunk_size=16)
        t_ref = np.asarray(ref.observe_batch(
            ref.steady_state_batch(params, q), params))
        th = sim.steady_state_batch(params, q)
        # the joined states stay on the eight devices, batch-sharded
        assert isinstance(th, jax.Array) and th.shape == (40, 564)
        assert th.sharding.is_equivalent_to(
            NamedSharding(sim.exec.mesh, P("data")), th.ndim)
        assert len(th.sharding.device_set) == 8
        th_ref = np.asarray(ref.steady_state_batch(params, q))
        assert np.abs(np.asarray(th) - th_ref).max() < 1e-6
        t = np.asarray(sim.observe_batch(th, params))
    assert np.abs(t - t_ref).max() < 1e-6


# ---------------------------------------------------------------------------
# the 8-device acceptance check stays in tier-1 via a subprocess
# ---------------------------------------------------------------------------
_SUBPROCESS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8 " \
    + os.environ.get("XLA_FLAGS", "")
import json
import numpy as np
import jax, jax.numpy as jnp
from repro.core import PackageFamily, build_family, make_2p5d_package

fam = PackageFamily(make_2p5d_package(16),
                    params=("grid_offsets", "htc_top"))
params = np.vstack([fam.base_params(), fam.sample_params(4, seed=0)])
q = np.full((5, 16), 3.0)               # B=5: non-divisible by 8
T = 8
qt = np.full((T, 5, 16), 2.0)
errs = {}
with jax.enable_x64(True):
    rc_ref = build_family(fam, "rc", dtype=jnp.float64)
    rc_8 = build_family(fam, "rc", dtype=jnp.float64, mesh=8)
    t_ref = np.asarray(rc_ref.observe_batch(
        rc_ref.steady_state_batch(params, q), params))
    t_8 = np.asarray(rc_8.observe_batch(
        rc_8.steady_state_batch(params, q), params))
    errs["rc_steady"] = float(np.abs(t_8 - t_ref).max())
    errs["rc_transient"] = float(np.abs(
        np.asarray(rc_8.simulate_family(params, qt, 0.01))
        - np.asarray(rc_ref.simulate_family(params, qt, 0.01))).max())
    dss_ref = build_family(fam, "dss", ts=0.01, dtype=jnp.float64)
    dss_8 = build_family(fam, "dss", ts=0.01, dtype=jnp.float64, mesh=8)
    errs["dss_transient"] = float(np.abs(
        np.asarray(dss_8.simulate_family(params, qt))
        - np.asarray(dss_ref.simulate_family(params, qt))).max())
    rom_ref = build_family(fam, "rom", ts=0.01, dtype=jnp.float64)
    rom_8 = build_family(fam, "rom", ts=0.01, dtype=jnp.float64, mesh=8,
                         basis=rom_ref.V)  # share the one template basis
    errs["rom_steady"] = float(np.abs(
        np.asarray(rom_8.observe_batch(
            rom_8.steady_state_batch(params, q), params))
        - np.asarray(rom_ref.observe_batch(
            rom_ref.steady_state_batch(params, q), params))).max())
    errs["rom_transient"] = float(np.abs(
        np.asarray(rom_8.simulate_family(params, qt))
        - np.asarray(rom_ref.simulate_family(params, qt))).max())
print(json.dumps(errs))
"""


@pytest.mark.skipif(
    len(jax.devices()) >= 8,
    reason="the @multi_device tests above cover this in-process; the "
           "subprocess exists to keep the acceptance bar in plain "
           "single-device tier-1 runs")
def test_sharded_families_subprocess():
    """rc/dss/rom sharded over 8 simulated devices match the
    single-device vmap path to <=1e-6 degC (f64, non-divisible B) — the
    PR-5 acceptance bar, enforced on every tier-1 run."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    import json
    errs = json.loads(out.stdout.strip().splitlines()[-1])
    for k, v in errs.items():
        assert v < 1e-6, (k, v)
