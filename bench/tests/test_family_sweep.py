"""Whole runs of the ``family_sweep`` traffic on the CPU, at sizes a test
run holds: the voxel fidelity against ``reference/voxel.py``, the RC
fidelity on a four-device mesh, the bfloat16 control and a planted CG
fault of the voxel solve (``test_harness.py``'s pattern)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import harness as H

FVM = "fvm_sweep.2p5d_64"
#: 28 x 28 x 14 = 10,976 voxels of the same package and sweep box
SMALL = {"build": {"dx_target": 1e-3, "dz_target": 0.15e-3, "max_slabs": 6,
                   "cg_tol": 1e-6},
         "shape": [14, 28, 28], "voxels": 10976}
TRAFFIC = {"candidates": 8, "chunk_size": 4, "wl1_traces": 1,
           "check_per_stratum": 1}


def _cg_unchanged(monkeypatch):
    """The voxel CG returns its start (zeros) and reports convergence."""
    from repro.core import fvm_ref
    from repro.kernels.fused_cg.ops import CGStats
    import jax.numpy as jnp

    def pcg(apply, diag, rhs, x0, tol, maxiter):
        b = rhs.shape[0]
        return x0, CGStats(jnp.zeros(b, jnp.int32), jnp.zeros(b, rhs.dtype),
                           jnp.ones(b, bool))

    monkeypatch.setattr(fvm_ref, "stencil_pcg", pcg)


def test_sound_fvm_sweep_is_correct(run_cell):
    line = run_cell(FVM, TRAFFIC, config=SMALL)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 8 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "sweep_candidates_per_s"}
    assert line["checks"]["max_err_c"]["value"] < 1e-3


def test_fvm_control_bfloat16_is_not_correct(run_cell):
    line = run_cell(FVM, TRAFFIC, config=SMALL, control=True)
    assert line["correct"] is False, line["checks"]


def test_fvm_planted_cg_fault_is_not_correct(run_cell, monkeypatch):
    """A window of one sweep: with no CG work the sweeps come so fast
    that a longer one would sample thousands of rows to check."""
    _cg_unchanged(monkeypatch)
    line = run_cell(FVM, TRAFFIC, config=SMALL, seconds=0.01)
    assert line["correct"] is False, line["checks"]


def test_program_without_cg_stats_stops_at_setup(monkeypatch):
    """A family model that keeps no ``last_cg_stats`` (as the voxel
    family had none) cannot show its solves converged: set-up raises."""
    import repro.core
    c = H.cell(H.spec(), FVM)
    c["config"].update(SMALL)
    c["traffic"].update(TRAFFIC)
    real = repro.core.build_family

    class Bare:
        def __init__(self, model):
            self.tags = model.tags

    monkeypatch.setattr(repro.core, "build_family",
                        lambda *a, **k: Bare(real(*a, **k)))
    with pytest.raises(H.BenchError, match="no CG stats"):
        H.driver("family_sweep").Run(c["config"], c["traffic"], 1, 1, 1.0)


def test_rc_sweep_on_four_devices_is_correct():
    """``sweep.2p5d_64.x4`` at a CPU size on four virtual devices: every
    chunk split over the mesh, and correct against the RC reference."""
    code = f"""
import json, sys
sys.path.insert(0, '.')
from bench import harness as H
H.devices = lambda chips, platform='tpu': {{'platform': 'cpu',
    'kind': 'TPU v5 lite', 'count': 4}}
real = H.cell
def cell(spec, name):
    c = real(spec, name)
    c['traffic'].update({json.dumps(
        {"candidates": 96, "chunk_size": 32, "wl1_traces": 1,
         "check_per_stratum": 2})})
    return c
H.cell = cell
import bench.run as R
sys.exit(R.main(['--workload', 'sweep.2p5d_64.x4', '--seed', '3000000019',
                 '--seconds', '1']))
"""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run([sys.executable, "-c", code], cwd=H.ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 96 and line["failed"] == 0
    assert "compiles_in_window lowered=0 compiled=0" in p.stderr


def test_fvm_work_count():
    """The roofline's work per live candidate-iteration at the cell's
    grid: 44 bytes a voxel in float32 less the missing boundary faces."""
    m = H.reader("fvm_cg.roofline")
    cfg = H.cell(H.spec(), FVM)["config"]
    flops, nbytes = m.work(cfg["shape"], 4)
    nz, ny, nx = cfg["shape"]
    assert nz * ny * nx == cfg["voxels"] == 169400
    f = m.faces(nz, ny, nx)
    assert f == 3 * 169400 - 110 * 110 - 2 * 14 * 110
    assert (flops, nbytes) == (4 * f + 15 * 169400, 4 * (f + 8 * 169400))
    assert np.isclose(nbytes / cfg["voxels"], 44, rtol=0.02)
