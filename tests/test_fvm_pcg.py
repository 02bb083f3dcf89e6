"""The voxel (FVM) fidelity's solver loop: every solve through the masked
batched PCG of ``kernels/fused_cg/ops.pcg_loop``, checked against the
benchmark's independent float64 voxel reference (``bench/reference/
voxel.py``, which imports nothing of the program), with per-candidate
``CGStats``, the unconverged counters and the ``fvm.*`` spans."""
import glob
import sys
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse.linalg as spla
from jax.profiler import ProfileData

from repro.core import PackageFamily, build, build_family, package_from_name
from repro.kernels.fused_cg.ops import (reset_unconverged_counts,
                                        unconverged_counts)
from repro.runtime import x64

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench.reference import package as rp  # noqa: E402
from bench.reference import voxel as rv  # noqa: E402

PRESET = "2p5d_16"
DX = 1e-3                  # 16 x 16 x 14 voxels: a CPU-sized grid
OPTS = {"dx_target": DX, "dz_target": 0.15e-3, "max_slabs": 6}
B = 3


@pytest.fixture(scope="module")
def case():
    """A placement x HTC family on 2p5d_16, seeded candidates and WL1-range
    powers, and the reference's observed steady state of each."""
    pkg, n_src = package_from_name(PRESET)
    fam = PackageFamily(pkg, params=("grid_offsets", "htc_top"))
    params = fam.sample_params(B, seed=2024)
    q = np.random.default_rng(7).uniform(0.75, 3.0, (B, n_src))
    ref_pkg = rp.make_package(PRESET)
    grids = [rv.voxelize(rp.candidate(ref_pkg, p), **OPTS) for p in params]
    want = np.stack([rv.steady_obs(g, qi) for g, qi in zip(grids, q)])
    return fam, params, q, grids, want


def _cols(model_tags, grid):
    return [list(model_tags).index(t) for t in grid.tags]


def test_family_steady_f64_matches_voxel_reference(case):
    """Same discretisation, both float64: only the CG tolerances differ
    (1e-12 here, 1e-10 in the reference), far below 1e-7 degC at a ~40 degC
    rise."""
    fam, params, q, grids, want = case
    with x64():
        sim = build_family(fam, "fvm", dtype=jnp.float64, cg_tol=1e-12,
                           **OPTS)
        got = np.asarray(sim.observe_batch(
            sim.steady_state_batch(params, q), params))
    assert np.abs(got[:, _cols(sim.tags, grids[0])] - want).max() < 1e-7


def test_family_steady_f32_matches_voxel_reference(case):
    """float32 at the default relative residual of 1e-6: the error is the
    CG stopping rule's, about 1e-6 of the ~40 degC rise summed over a few
    hundred iterations in float32; 5e-4 degC leaves ten times room over
    the ~3e-5 read, and a bfloat16 solve (over 1 degC off) fails it."""
    fam, params, q, grids, want = case
    sim = build_family(fam, "fvm", chunk_size=2, **OPTS)
    got = np.asarray(sim.observe_batch(
        sim.steady_state_batch(params, q), params))
    assert np.abs(got[:, _cols(sim.tags, grids[0])] - want).max() < 5e-4
    assert (want - 25.0).max() > 20.0          # heat actually flows
    bf16 = rv.control_obs(grids, q, jnp.bfloat16)
    assert np.abs(bf16 - want).max() > 5e-4


def test_single_package_steady_matches_voxel_reference(case):
    """``build(pkg, "fvm")`` of one candidate: the same loop at B = 1 and
    the same float32 tolerance as the family."""
    fam, params, q, grids, want = case
    model = build(fam.instantiate(params[1]), "fvm", **OPTS)
    got = np.asarray(model.observe(model.steady_state(q[1])))
    assert np.abs(got[_cols(model.tags, grids[1])] - want[1]).max() < 5e-4
    stats = model.last_cg_stats
    assert stats.iterations.shape == (1,) and bool(stats.converged[0])


def test_family_last_cg_stats_are_per_candidate(case):
    fam, params, q, _, _ = case
    sim = build_family(fam, "fvm", chunk_size=2, **OPTS)
    assert sim.last_cg_stats is None
    sim.steady_state_batch(params, q)
    stats = sim.last_cg_stats
    for leaf in stats:
        assert np.shape(leaf) == (B,)
    assert np.asarray(stats.converged).all()
    its = np.asarray(stats.iterations)
    assert (its > 10).all() and (its < 4 * sim.cg_maxiter).all()
    assert (np.asarray(stats.residual) <= sim.cg_tol).all()


@pytest.mark.parametrize("where", ["family", "single"])
def test_small_maxiter_marks_unconverged(case, where):
    """A cap far below what the tolerance needs: every row comes back
    unconverged, the site warns once and its counter counts each call."""
    fam, params, q, _, _ = case
    reset_unconverged_counts()
    if where == "family":
        model = build_family(fam, "fvm", cg_maxiter=2, **OPTS)
        solve = lambda: model.steady_state_batch(params, q)
        site = "fvm family steady CG"
    else:
        model = build(fam.instantiate(params[0]), "fvm", cg_maxiter=2,
                      **OPTS)
        solve = lambda: model.steady_state(q[0])
        site = "fvm steady CG"
    with pytest.warns(RuntimeWarning, match="iteration cap"):
        solve()
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # warned once per site
        solve()
    stats = model.last_cg_stats
    assert not np.asarray(stats.converged).any()
    assert (np.asarray(stats.iterations) == 8).all()     # 4 x cg_maxiter
    assert unconverged_counts() == {site: 2}
    reset_unconverged_counts()


def test_family_transient_matches_single_package(case):
    """The family's implicit-Euler rollout (one masked PCG per step over
    the batch) against each candidate's own ``make_simulator``."""
    fam, params, q, _, _ = case
    steps, dt = 6, 0.01
    q_t = np.broadcast_to(q, (steps, B, q.shape[1]))
    sim = build_family(fam, "fvm", **OPTS)
    fam_obs = np.asarray(sim.simulate_family(params, q_t, dt))
    assert fam_obs.shape == (steps, B, len(sim.tags))
    for b in range(B):
        model = build(fam.instantiate(params[b]), "fvm", **OPTS)
        simulate = model.make_simulator(dt)
        single = np.asarray(simulate(model.zero_state(), q_t[:, b]))
        cols = [model.tags.index(t) for t in sim.tags]
        assert np.abs(fam_obs[:, b] - single[:, cols]).max() < 1e-3
        assert simulate.last_stats.iterations.shape == (steps, 1)


def test_voxel_reference_matches_splu():
    """The reference's CG at its relative residual of 1e-10 against a
    sparse direct solve of the same operator."""
    pkg = rp.candidate(rp.make_package("2p5d_16"),
                       np.r_[np.full(8, 2e-4), 4000.0])
    grid = rv.voxelize(pkg, **OPTS)
    q = np.linspace(0.75, 3.0, len(grid.sources))
    direct = spla.splu(grid.operator().tocsc()).solve(grid.src.T @ q)
    want = grid.obs @ direct + grid.t_ambient
    assert np.abs(rv.steady_obs(grid, q) - want).max() < 1e-7
    assert grid.shape == (14, 16, 16) and len(grid.tags) == 16


def _spans(path):
    """(name, start, end) of every ``mfit.*`` span and every XLA program
    run in a trace."""
    out, programs = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("mfit."):
                    out.append((ev.name[5:], ev.start_ns,
                                ev.start_ns + ev.duration_ns))
                elif line.name == "XLA Modules":
                    programs.append(ev.name)
    return out, programs


def test_fvm_spans_open(case, tmp_path):
    """Under the profiler the family's steady solve, its convergence
    check and its observation each open their span around the executor's
    and the steady program carries the ``fvm_steady`` name."""
    fam, params, q, _, _ = case
    sim = build_family(fam, "fvm", chunk_size=2, **OPTS)
    jax.profiler.start_trace(str(tmp_path))
    try:
        sim.observe_batch(sim.steady_state_batch(params, q), params)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans, programs = _spans(path)
    names = [n for n, _, _ in spans]
    for name in ("fvm.steady", "fvm.check", "fvm.observe"):
        assert names.count(name) == 1, names

    def inside(inner, outer):
        (_, s, e), = [x for x in spans if x[0] == outer]
        return [x for x in spans if x[0] == inner and s <= x[1]
                and x[2] <= e]

    assert len(inside("fvm.check", "fvm.steady")) == 1
    assert len(inside("exec.run", "fvm.steady")) == 1
    assert len(inside("exec.run", "fvm.observe")) == 1
    assert len(inside("exec.dispatch", "fvm.steady")) == 2   # 2 chunks
    if programs:        # the CPU backend may write no program line
        assert any("fvm_steady" in p for p in programs)
