"""Whole runs of the harness on the CPU, at sizes a test run holds.

The look for a chip is skipped (``run_cell`` in conftest); everything
else runs as on the chip: set-up, window, the check against the host
float64 reference. A sound run must come out correct; the control
(the reference, computed in bfloat16, put in the program's place: the
sweeps' Jacobi-PCG, the oracle's exact zero-order hold) and each fault
planted in the timed path must come out not correct.
"""
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness as H

SWEEP = {"candidates": 96, "chunk_size": 32, "wl1_traces": 1,
         "check_per_stratum": 2}
FLEET = {"clients": 4, "check_requests": 24, "wait_s": 120}
PACED = {**FLEET, "clients": None, "rate_per_s": 12.0}


def _cg_unchanged(monkeypatch):
    """The CG step returns its state unchanged (the warm start)."""
    from repro.core import rc_model
    from repro.kernels.fused_cg.ops import CGStats
    import jax.numpy as jnp

    def pcg(self, gvals, gconv, rhs, x0):
        b = rhs.shape[0]
        return x0, CGStats(jnp.zeros(b, jnp.int32), jnp.zeros(b, rhs.dtype),
                           jnp.ones(b, bool))

    monkeypatch.setattr(rc_model.RCFamilyModel, "_pcg", pcg)


def _sweep_half(monkeypatch):
    """Half of each sweep left out: its answers copied from the rest."""
    from repro.core import rc_model
    real = rc_model.RCFamilyModel.steady_state_batch

    def steady(self, params, q):
        h = params.shape[0] // 2
        th = np.asarray(real(self, params[:h], q[:h]))
        return np.concatenate([th, th[:params.shape[0] - h]])

    monkeypatch.setattr(rc_model.RCFamilyModel, "steady_state_batch", steady)


def _sweep_altered(monkeypatch):
    """One chiplet's temperature altered where the answers are made."""
    from repro.core import rc_model
    real = rc_model.RCFamilyModel.observe_batch

    def observe(self, theta, params):
        out = np.array(real(self, theta, params))
        out[:, 0] += 0.05
        return out

    monkeypatch.setattr(rc_model.RCFamilyModel, "observe_batch", observe)


def _dtpm_unchanged(monkeypatch):
    """The DTPM step returns the thermal state it was given."""
    from repro.core import dtpm
    real = dtpm.ThermalManager.update

    def update(self, state, powers):
        new, info = real(self, state, powers)
        return new._replace(theta=state.theta), info

    monkeypatch.setattr(dtpm.ThermalManager, "update", update)


def _fleet_half(monkeypatch):
    """Half of each batch left out: those requests get another's answer."""
    from repro.serving import oracle
    real = oracle.ThermalOracle._answer_dtpm

    def answer(self, model, group):
        h = max(1, len(group) // 2)
        out = real(self, model, group[:h])
        return out + out[:len(group) - h]

    monkeypatch.setattr(oracle.ThermalOracle, "_answer_dtpm", answer)


def _fleet_altered(monkeypatch):
    """One step of every DTPM answer altered where it is made."""
    from repro.core import dtpm
    real = dtpm.ThermalManager.serve_trace

    def serve(self, powers):
        tmax, tel = real(self, powers)
        tmax = np.array(tmax)
        tmax[100] += 0.05
        return tmax, tel

    monkeypatch.setattr(dtpm.ThermalManager, "serve_trace", serve)


def test_sound_sweep_is_correct(run_cell):
    line = run_cell("sweep.2p5d_64", SWEEP)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 96 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "sweep_candidates_per_s"}
    assert list(line)[-1] == "checks"


def test_sound_fleet_is_correct(run_cell):
    line = run_cell("dtpm_fleet.2p5d_64", FLEET, seconds=2.0)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 4
    assert set(line["metrics"]) == {"setup_s", "oracle_answers_per_s"}
    assert line["metrics"]["oracle_answers_per_s"]["value"] > 0


def test_sound_paced_fleet_is_correct(run_cell):
    """The open loop the knee sweep offers: every request sent on time."""
    line = run_cell("dtpm_fleet.2p5d_64", PACED, seconds=2.0)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] == 24


@pytest.mark.parametrize("workload,traffic,seconds", [
    ("sweep.2p5d_64", SWEEP, 1.0),
    ("dtpm_fleet.2p5d_64", FLEET, 2.0)])
def test_control_bfloat16_is_not_correct(run_cell, workload, traffic,
                                         seconds):
    line = run_cell(workload, traffic, seconds=seconds, control=True)
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("workload,traffic,seconds,fault", [
    ("sweep.2p5d_64", SWEEP, 1.0, _cg_unchanged),
    ("sweep.2p5d_64", SWEEP, 1.0, _sweep_half),
    ("sweep.2p5d_64", SWEEP, 1.0, _sweep_altered),
    ("dtpm_fleet.2p5d_64", FLEET, 2.0, _dtpm_unchanged),
    ("dtpm_fleet.2p5d_64", {**FLEET, "clients": 16,
                            "check_requests": 80}, 2.0, _fleet_half),
    ("dtpm_fleet.2p5d_64", FLEET, 2.0, _fleet_altered),
], ids=lambda x: getattr(x, "__name__", None))
def test_planted_fault_is_not_correct(run_cell, monkeypatch, workload,
                                      traffic, seconds, fault):
    fault(monkeypatch)
    line = run_cell(workload, traffic, seconds=seconds)
    assert line["correct"] is False, line["checks"]


def _run(args, cwd, env=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})}
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(["bench/run.py", "--workload", "sweep.2p5d_64", "--seed",
              "3000000019", "--seconds", "1"], cwd=H.ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_bare_benchmark_directory_exits_nonzero(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: the system
    under test is absent, so even past the chip check there is no
    result."""
    shutil.copy(H.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(H.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import sys; sys.path.insert(0, '.');"
            "from bench import harness as H;"
            "H.devices = lambda chips, platform='tpu': "
            "{'platform': 'tpu', 'kind': 'TPU v5 lite', 'count': 1};"
            "import bench.run as R;"
            "sys.exit(R.main(['--workload', 'sweep.2p5d_64', '--seed', '1',"
            " '--seconds', '1']))")
    p = _run(["-c", code], cwd=tmp_path,
             env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert p.returncode != 0
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
