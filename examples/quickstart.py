"""Quickstart: the MFIT multi-fidelity model family in ~60 lines.

Builds the paper's 16-chiplet 2.5D system once, then walks the fidelity
ladder (paper Fig. 2) by STRING through the fidelity registry — the same
geometry served by the FVM golden reference, the thermal RC model, and
the DSS model, all exposing the common ThermalSimulator protocol — and
prints cross-fidelity agreement and speedups.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import time

import numpy as np

from repro.core import build, make_2p5d_package
from repro.core.workloads import wl1

DT = 0.01

pkg = make_2p5d_package(16)
print(f"package: {pkg.name}, {len(pkg.layers)} layers, "
      f"{pkg.length*1e3:.1f} mm square")

q = wl1(16, dt=DT, t_stress=2.0, t_prbs=3.0, t_cool=2.0)
print(f"workload: WL1, {len(q)} steps of {DT}s")

# One geometry, three fidelities, one protocol. Build (geometry -> ready
# model, incl. DSS regeneration) is timed separately from the rollout —
# the paper's Fig. 2 ladder is about SIMULATION speed.
sims, obs, t_build, t_roll = {}, {}, {}, {}
for fidelity in ("fvm", "rc", "dss"):
    t0 = time.time()
    sim = build(pkg, fidelity, **({"ts": DT} if fidelity == "dss" else {}))
    rollout = sim.make_simulator(DT)
    t_build[fidelity] = time.time() - t0
    obs[fidelity] = np.asarray(rollout(sim.zero_state(), q))  # warm + run
    t0 = time.time()
    np.asarray(rollout(sim.zero_state(), q))
    t_roll[fidelity] = time.time() - t0
    sims[fidelity] = sim

size = {"fvm": f"{sims['fvm'].vm.n_vox} voxels",
        "rc": f"{sims['rc'].net.n} nodes",
        "dss": f"{sims['dss'].n} states"}
print(f"[FVM  ] {size['fvm']:>12s}   peak {obs['fvm'].max():6.1f} C   "
      f"build {t_build['fvm']:5.2f}s  rollout {t_roll['fvm']:7.3f}s")
print(f"[RC   ] {size['rc']:>12s}   peak {obs['rc'].max():6.1f} C   "
      f"build {t_build['rc']:5.2f}s  rollout {t_roll['rc']:7.3f}s   "
      f"MAE vs FVM {np.abs(obs['rc']-obs['fvm']).mean():.3f} C")
print(f"[DSS  ] {size['dss']:>12s}   peak {obs['dss'].max():6.1f} C   "
      f"build {t_build['dss']:5.2f}s  rollout {t_roll['dss']:7.3f}s   "
      f"MAE vs RC  {np.abs(obs['dss']-obs['rc']).mean():.3f} C")
print(f"\nrollout speedups: RC is {t_roll['fvm']/t_roll['rc']:.0f}x "
      f"faster than FVM; DSS is {t_roll['rc']/t_roll['dss']:.1f}x faster "
      f"than RC ({t_roll['fvm']/t_roll['dss']:.0f}x vs FVM)")

# Level 2 of the API: a whole design space in one device call. A
# PackageFamily shares the template's topology; placement/cooling
# parameters ride a batch axis (see examples/thermal_dse.py for the full
# sweep, and examples/thermal_opt.py for the gradient-based optimizer
# that beats the 10k-candidate sweep at ~5% of its solves).
from repro.core import PackageFamily, build_family  # noqa: E402

family = PackageFamily(pkg, params=("grid_offsets",))
fsim = build_family(family, "rc")
params = family.sample_params(8, seed=0)
qb = np.tile(q[200][None], (8, 1))
temps = np.asarray(fsim.observe_batch(
    fsim.steady_state_batch(params, qb), params))
print(f"\n[family] {family.n_params}-parameter placement family, "
      f"8 candidates in one call: peak spread "
      f"{temps.max(axis=1).min():.2f}..{temps.max(axis=1).max():.2f} C")

# The ROM rung: project the RC network onto a Krylov moment-matching
# basis once, then every transient step is a dense r x r op — cost
# independent of the node count, accuracy within ~0.1 C of the full DSS.
rom = build(pkg, "rom", ts=DT)
roll_rom = rom.make_simulator(DT)
obs_rom = np.asarray(roll_rom(rom.zero_state(), q))  # warm + run
t0 = time.time()
np.asarray(roll_rom(rom.zero_state(), q))
t_rom = time.time() - t0
print(f"\n[ROM  ] {rom.r:4d} of {rom.n_full} states "
      f"({rom.reduction_ratio:.1f}x smaller)   peak "
      f"{obs_rom.max():6.1f} C   rollout {t_rom:7.3f}s   "
      f"{t_roll['rc']/t_rom:.0f}x faster per step than RC, "
      f"{t_roll['dss']/t_rom:.1f}x than DSS; max err vs DSS "
      f"{np.abs(obs_rom-obs['dss']).max():.3f} C")

# The solver tier: the same build() strings scale past the paper's
# systems. solver="auto" keeps the exact dense Cholesky for small
# networks and switches to the matrix-free CG path (no N x N matrix
# ever built) above the measured crossover — here the 64-chiplet
# system picks it automatically. Each CG iteration runs as ONE fused
# kernel launch (kernels/fused_cg), and every solve reports iterations /
# final relative residual / a converged flag.
from repro.core import make_2p5d_package as _mk  # noqa: E402

big = _mk(64)
for solver in ("dense", "auto"):
    sim = build(big, "rc", solver=solver)
    t0 = time.time()
    peak = float(np.asarray(sim.observe(
        sim.steady_state(np.full(64, 3.0)))).max())
    print(f"[solver] 2p5d_64 ({sim.net.n} nodes) solver={solver!r:8s}"
          f" -> {sim.solver:5s} steady peak {peak:6.1f} C "
          f"in {time.time()-t0:5.2f}s")
st = sim.last_cg_stats
if st is not None:
    print(f"[solver] cg steady stats: {int(st.iterations)} fused "
          f"iterations, residual {float(st.residual):.1e}, "
          f"converged={bool(st.converged)}")

# The ladder made automatic: build(pkg, "auto", tol=...) routes each
# query to the cheapest rung whose CERTIFIED error bound meets the
# target, escalating when the certificate fails. The certificate is an
# a-posteriori residual bound (core/router.py), so the answer carries
# its own error bar — no reference run needed. Same query, two targets:
# the loose one certifies on the reduced rung, the tight one escalates
# to the full-order exact-ZOH reference.
router = build(pkg, "auto", tol=1e-2, ts=DT)
q_short = q[:100]
# certificates are linear in the drive — normalize so the ROM bound
# sits around 1e-2 and the tol sweep below straddles it
q_short = q_short * (8e-3 / router.query_transient(
    q_short, rung="rom").certified)
for tol in (1e-1, 1e-4):
    ans = router.query_transient(q_short, tol=tol)
    print(f"[auto ] tol={tol:.0e} -> rung {ans.rung!r:6s} certified "
          f"<= {ans.certified:.2e} C (margin {ans.margin:+.2e}, "
          f"{ans.escalations} escalation(s))")

# Level 3 of the API: don't build models, ASK a service. The thermal
# oracle (repro.serving, examples/thermal_service.py) keeps warm
# content-addressed models behind a continuous-batched, deadline-aware
# queue — concurrent steady/transient/DTPM queries answered on the ROM
# rung in microseconds, repeat geometries skipping every one-time build.
print("\nnext: PYTHONPATH=src python examples/thermal_service.py "
      "(the always-on thermal-oracle service over this ladder)")
