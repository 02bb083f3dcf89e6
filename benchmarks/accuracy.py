"""Paper Table 8: MAE + temperature-violation-prediction accuracy of
thermal RC / DSS / ROM / HotSpot-like / 3D-ICE-like / PACT-like vs the
FVM golden reference, across systems x workloads. The ROM row tracks the
DSS row to within its <=0.1 degC projection error — same accuracy class,
node-count-independent per-step cost.

Full paper grid = {16,36,64-chip 2.5D, 16x3 3D} x WL1-6 at 40-55 s traces;
the default here runs a reduced grid/time_scale sized for this container's
CPU (pass --full for the whole thing — hours).
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

from repro.core import build, package_from_name
from repro.core.workloads import P2P5D, P3D, get_workload
from repro.runtime import use_compile_cache

T_VIOLATION = 85.0  # paper §5.4
DT = 0.01


def violation_accuracy(ref_temps, model_temps, margin: float = 1.0):
    """% of reference violations flagged by the model (paper metric;
    models conservatively flag within 1 C of the threshold)."""
    ref_v = ref_temps > T_VIOLATION
    mdl_v = model_temps > (T_VIOLATION - margin)
    n_ref = ref_v.sum()
    if n_ref == 0:
        return 100.0
    return 100.0 * float((ref_v & mdl_v).sum()) / float(n_ref)


# models are workload-independent: cache them per (system, dx) so the
# grid pays geometry -> model once per system, not once per cell (the
# FVM reference voxelization and the ROM basis construction dominate)
_MODEL_CACHE: dict = {}


def _get_model(system: str, pkg, fidelity: str, dx: float):
    key = (system, dx, fidelity)
    if key not in _MODEL_CACHE:
        opts = {"dx_target": dx, "cg_tol": 1e-6} if fidelity == "fvm" \
            else {"ts": DT} if fidelity in ("dss", "rom") else {}
        _MODEL_CACHE[key] = build(pkg, fidelity, **opts)
    return _MODEL_CACHE[key]


def run_cell(system: str, workload: str, time_scale: float, dx: float,
             verbose: bool = True) -> dict:
    pkg, n_src = package_from_name(system)
    spec = P3D if system.startswith("3d") else P2P5D
    q = get_workload(workload, n_src, dt=DT, spec=spec,
                     time_scale=time_scale)

    fvm = _get_model(system, pkg, "fvm", dx)
    ref = np.asarray(fvm.make_simulator(DT)(fvm.zero_state(), q))

    out = {"system": system, "workload": workload, "models": {}}
    names = {"rc": "thermal_rc", "dss": "dss", "rom": "rom",
             "hotspot": "hotspot", "3dice": "3dice", "pact": "pact"}
    for fidelity, label in names.items():
        mdl = _get_model(system, pkg, fidelity, dx)
        obs = np.asarray(mdl.make_simulator(DT)(mdl.zero_state(), q))
        out["models"][label] = _metrics(ref, obs)
    if verbose:
        row = "  ".join(f"{k}={v['mae']:.2f}C/{v['viol_acc']:.0f}%"
                        for k, v in out["models"].items())
        print(f"[accuracy] {system:8s} {workload}: {row}", flush=True)
    return out


def _metrics(ref, obs):
    return {"mae": float(np.abs(ref - obs).mean()),
            "max_err": float(np.abs(ref - obs).max()),
            "viol_acc": violation_accuracy(ref, obs)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out", default="benchmarks/artifacts/accuracy.json")
    args = ap.parse_args(argv)
    use_compile_cache()
    if args.full:
        systems = ["2p5d_16", "2p5d_36", "2p5d_64", "3d_16x3"]
        workloads = ["WL1", "WL2", "WL3", "WL4", "WL5", "WL6"]
        ts, dx = 1.0, 0.25e-3
    else:
        systems = ["2p5d_16", "3d_16x3"]
        workloads = ["WL1", "WL2", "WL6"]
        ts, dx = 0.15, 0.5e-3
    results = [run_cell(s, w, ts, dx) for s in systems for w in workloads]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    # csv summary: name,mae,viol
    for r in results:
        for m, v in r["models"].items():
            print(f"table8,{r['system']},{r['workload']},{m},"
                  f"{v['mae']:.3f},{v['viol_acc']:.1f}")
    return results


if __name__ == "__main__":
    main()
