"""Gradient-based placement optimization (ISSUE 10): beating the random
sweep at a few percent of its solve budget.

``examples/thermal_dse.py`` ranks placements by brute force — B random
candidates, B steady solves. This walkthrough spends those solves on
GRADIENT STEPS instead: the cg tier's peak steady temperature is
reverse-differentiable through the implicit-adjoint fused-CG solve
(``kernels/fused_cg/adjoint.py`` — forward pass unchanged, backward pass
ONE extra CG solve of the self-adjoint system), so a multi-start
projected Adam (``core/optimize.py``) walks the 16-chiplet placement
family downhill on a temperature-annealed smooth-max peak objective.

Three acts:
  1. the B=10k random sweep baseline (chunk-streamed, as in thermal_dse);
  2. ``optimize_family`` capped at 5% of the sweep's solve count —
     finds a COOLER placement, with the adjoint-solve accounting printed
     from the solver's own stats registry;
  3. the same optimizer on a TRANSIENT whole-trace peak through the ROM
     rung (reverse-differentiated r x r ZOH rollout — node-count
     independent, no N x N matrix in the gradient graph).

Run:  PYTHONPATH=src python examples/thermal_opt.py
"""
import time

import numpy as np

import jax.numpy as jnp

from repro.core import PackageFamily, build_family, make_2p5d_package, \
    optimize_family
from repro.core.rc_model import RCFamilyModel
from repro.kernels.fused_cg import adjoint
from repro.runtime import x64

pkg = make_2p5d_package(16)
family = PackageFamily(pkg, params=("grid_offsets",))
print(f"{family}\nparams: {', '.join(family.param_names)}")

# workload: the 4 center chiplets run hot (3 W), the rest idle (0.4 W)
HOT, IDLE = 3.0, 0.4
hot = [5, 6, 9, 10]
q = np.full(16, IDLE)
q[hot] = HOT

with x64():
    # -----------------------------------------------------------------
    # act 1: the brute-force baseline — 10k candidates, 10k solves
    # -----------------------------------------------------------------
    model = RCFamilyModel(family, dtype=jnp.float64, solver="cg",
                          chunk_size=512)
    B = 10_000
    cand = family.sample_params(B, seed=0)
    t0 = time.time()
    peaks = np.asarray(model.peak_steady(
        cand, np.broadcast_to(q, (B, 16))))
    t_sweep = time.time() - t0
    sweep_best = peaks.min()
    print(f"\nrandom sweep: B={B} solves in {t_sweep:.1f}s, "
          f"best peak {sweep_best:.3f} C")

    # -----------------------------------------------------------------
    # act 2: gradient descent on the same family, 5% of the budget
    # -----------------------------------------------------------------
    budget = B // 20                      # 500 solve-equivalents
    adjoint.reset_adjoint_stats()
    t0 = time.time()
    res = optimize_family(model, q, n_starts=6, method="adam", steps=40,
                          lr=0.1, tau=(2.0, 0.05), budget=budget, seed=0)
    t_opt = time.time() - t0
    print(f"\noptimizer ({res.method}, {res.n_iters} iterations, "
          f"6 starts): best peak {res.best_value:.3f} C in {t_opt:.1f}s")
    print(f"  solve-equivalents: {res.n_solve_equiv} "
          f"({100 * res.n_solve_equiv / B:.1f}% of the sweep; a grad "
          f"eval is priced forward + adjoint = 2)")
    counts = adjoint.solve_counts()
    site = "rc family peak_steady adjoint CG"
    stats = adjoint.last_stats(site)
    print(f"  adjoint registry: {counts[site]['rows']} adjoint row "
          f"solves, last solve {int(np.max(stats.iterations))} CG "
          f"iterations, residual {float(np.max(stats.residual)):.1e}, "
          f"converged={bool(np.all(stats.converged))}")
    print(f"  beats the {B}-candidate sweep by "
          f"{sweep_best - res.best_value:+.3f} C at "
          f"{t_sweep / max(t_opt, 1e-9):.1f}x less wall-clock")
    assert res.best_value <= sweep_best

    # -----------------------------------------------------------------
    # act 3: transient whole-trace peak through the ROM rung
    # -----------------------------------------------------------------
    rom = build_family(family, "rom", dtype=jnp.float64)
    T = 40
    ramp = np.linspace(0.5, 1.5, T)[:, None]   # a power ramp on the trace
    qt = np.tile(q, (T, 1)) * ramp
    res_t = optimize_family(rom, objective="peak_transient", q_traj=qt,
                            dt=0.01, n_starts=4, steps=15, budget=250,
                            seed=0)
    base_t = float(rom.peak_transient(family.base_params()[None],
                                      qt, 0.01)[0])
    print(f"\nROM transient objective (T={T} steps, r={rom.r}): template "
          f"whole-trace peak {base_t:.3f} C -> optimized "
          f"{res_t.best_value:.3f} C "
          f"({res_t.n_solve_equiv} ROM solve-equivalents; the rollout "
          f"gradient is an r x r scan — no N x N matrix anywhere)")
    assert res_t.best_value <= base_t + 1e-9
