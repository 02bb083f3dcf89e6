"""Paper Fig. 8 + §5.3: execution-time comparison of the model family.

Measures, per system size and per registered fidelity:
  * model-BUILD time (geometry -> ready simulator), including the
    vectorized network assembly vs the seed's O(n^2) pair-loop builder
    (``core/assembly_ref.py``) — the speedup tracked across PRs;
  * simulation wall time and per-step time for a WL1 trace with
    thermal RC (prefactored BE) vs DSS vs HotSpot-like (RK4) vs
    3D-ICE-like (per-step LU) vs PACT-like (TRAP);
  * DSS regeneration latency (paper: "a few milliseconds") and the
    batched-DSE throughput unique to the TPU formulation;
  * the ``dse_sweep`` section: a B-candidate placement family evaluated
    through ``build_family`` (one symbolic assembly + one device call,
    template-preconditioned CG) vs the same candidates through a
    per-package ``build()`` loop — both in float64 so the two paths can be
    checked against each other to <=1e-6 degC;
  * the ``sparse_solver`` section: dense Cholesky/solve vs the
    matrix-free CG tier (``solver="cg"``, ``kernels/coo_matvec``) on a
    node-count ladder up to the 256-chiplet 2.5D and 16x6-stack 3D
    systems, plus the measured steady crossover that ``solver="auto"``
    keys on (with a calibration warning when the constant drifts >2x
    from the measurement);
  * the ``fused_cg`` section: the fused CG solve —
    per-iteration and end-to-end steady/transient times of the CG tier
    vs the dense tier, with the per-solve iteration counts / final
    residuals / converged flags the solver reports, and the refreshed
    dense-vs-CG steady crossover measured on it;
  * the ``rom`` section: the Krylov moment-matching ROM rung — basis
    construction cost, reduction ratio N/r, per-step transient time vs
    the dense tier (the node-count-independent headline) and max
    observation error vs the full-order exact-ZOH response in f64;
  * the ``sharded_dse`` section (PR 5): the family execution layer —
    RC steady sweeps over meshes of {1, 2, 4, 8} devices, as many as
    the process has (``mesh=`` on ``build_family``), and the B=10k
    chunk-streamed sweep (``chunk_size=``), with speedup vs the
    single-device vmap path and the sweep's growth of the process RSS
    high-water as the bounded-memory evidence. Every config runs in
    this one process, on submeshes of its devices;
  * the ``serving`` section (PR 7): the thermal-oracle service
    (``repro.serving``) — cold-vs-warm content-addressed model build
    time, warmed sequential p50/p99 latency for steady and ROM-transient
    queries (the sub-ms headline), and threaded-storm throughput with
    mean batch occupancy from the continuous batcher;
  * the ``dse_opt`` section (ISSUE 10): gradient-based placement DSE —
    the multi-start projected-Adam optimizer (gradients through the
    implicit-adjoint fused-CG steady solve, annealed smooth-max peak
    objective) vs the B=10k random sweep on the same family/workload,
    capped at 5% of the sweep's solve count (grad evals priced at
    forward + one adjoint solve = 2); records both peaks,
    ``beats_sweep``, wall times, the adjoint registry's CGStats
    (iterations / residual / converged) and a ROM-rung transient-peak
    optimization running end to end;
  * the ``router`` section (ISSUE 8): the adaptive fidelity router
    (``build(pkg, "auto", tol=...)``) on every Table-6 system — per
    (system, tol): the rung the router chose, its certified error bound
    vs the error MEASURED against an independent full-order f64
    reference (scipy LU steady / whitened scipy-Pade exact ZOH
    transient), and the routing+certification overhead. Every row
    asserts certified >= measured — a certificate that under-reports is
    a CI failure, not a logged number.

All models are obtained through the fidelity registry. Results land in a
machine-readable ``BENCH_exec_time.json`` at the repo root so the perf
trajectory is tracked across PRs. Absolute times are this container's CPU;
the reproduced claim is the ORDERING and the orders-of-magnitude
separation (DESIGN.md §9).

``--smoke`` runs the smallest system with a reduced trace and sweep — the
CI benchmark step uses it to keep the artifact fresh on every push.
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import PackageFamily, build, build_family, continuous_ss, \
    discretize, discretize_rc, make_2p5d_package, package_from_name, \
    zoh_discretize
from repro.core.assembly_ref import build_network_ref
from repro.core.fidelity import SOLVER_CROSSOVER_NODES
from repro.core.rc_model import build_network
from repro.core.workloads import P2P5D, P3D, wl1
from repro.runtime import use_compile_cache, x64

SIM_FIDELITIES = ("rc", "dss", "rom", "hotspot", "3dice", "pact")


def _time(fn, warmup: int = 1, reps: int = 3) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _host_time(fn, reps: int = 3) -> float:
    """min wall time of a host-side (non-jax) callable."""
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _package(system: str):
    pkg, n_src = package_from_name(system)
    return pkg, n_src, P3D if system.startswith("3d") else P2P5D


def bench_assembly(system: str, legacy_reps: int = 1) -> dict:
    """Network-assembly time: vectorized (ours) vs seed pair loops."""
    pkg, _, _ = _package(system)
    grid = discretize(pkg)
    t_grid = _host_time(lambda: discretize(pkg))
    t_vec = _host_time(lambda: build_network(pkg, grid=grid))
    t_leg = _host_time(lambda: build_network_ref(pkg, grid=grid),
                       reps=legacy_reps)
    out = {"system": system, "nodes": grid.n,
           "discretize_s": t_grid,
           "assembly_vectorized_s": t_vec,
           "assembly_legacy_s": t_leg,
           "assembly_speedup": t_leg / max(t_vec, 1e-12)}
    print(f"[assembly ] {system:8s} n={grid.n:5d} "
          f"vectorized={t_vec*1e3:7.2f}ms legacy={t_leg:7.3f}s "
          f"speedup={out['assembly_speedup']:.0f}x", flush=True)
    return out


def run_system(system: str, n_steps: int, verbose=True) -> dict:
    pkg, n_src, spec = _package(system)
    dt = 0.01
    q = wl1(n_src, dt=dt, spec=spec)[:n_steps].astype(np.float32)

    out = {"system": system, "n_steps": n_steps, "nodes": {},
           "build_s": {}, "times": {}, "per_step_s": {}}

    def record(name, model, sim, state0, warmup=1, reps=3):
        out["nodes"][name] = model.net.n if hasattr(model, "net") \
            else model.n
        t = _time(lambda: sim(state0, q), warmup=warmup, reps=reps)
        out["times"][name] = t
        out["per_step_s"][name] = t / n_steps

    build(pkg, "dss", ts=dt)  # warm the expm jit before any timing
    # fidelity build times (geometry -> ready model, host side); the model
    # constructed inside the timed call is kept and reused below
    built = {}
    for f in SIM_FIDELITIES:
        opts = {"ts": dt} if f in ("dss", "rom") else {}
        def _build(f=f, opts=opts):
            built[f] = build(pkg, f, **opts)
        out["build_s"][f] = _host_time(_build, reps=1)

    rc = built["rc"]
    record("thermal_rc", rc, rc.make_simulator(dt), rc.zero_state())

    t0 = time.perf_counter()
    discretize_rc(rc, ts=dt * 0.5)
    out["times"]["dss_regeneration"] = time.perf_counter() - t0
    dss = built["dss"]
    record("dss", dss, dss.make_simulator(dt), dss.zero_state())
    rom = built["rom"]
    record("rom", rom, rom.make_simulator(dt), rom.zero_state())

    # batched DSE rollout (TPU-native capability; 64 candidates at once)
    B = 64
    zb = dss.zero_state(batch=B)
    qb = np.tile(q[:, None, :], (1, B, 1))
    t_batch = _time(lambda: dss.simulate_batch(zb, qb, dt))
    out["times"]["dss_batched_64"] = t_batch
    out["times"]["dss_per_candidate"] = t_batch / B

    for name in ("hotspot", "3dice", "pact"):
        mdl = built[name]
        record(name, mdl, mdl.make_simulator(dt), mdl.zero_state(),
               warmup=1, reps=1)
    if verbose:
        t = out["times"]
        print(f"[exec_time] {system:8s} rc={t['thermal_rc']:.3f}s "
              f"dss={t['dss']:.4f}s regen={t['dss_regeneration']*1e3:.1f}ms"
              f" hotspot={t['hotspot']:.2f}s 3dice={t['3dice']:.2f}s"
              f" pact={t['pact']:.2f}s", flush=True)
    return out


def bench_dse_sweep(system: str = "2p5d_16", n_candidates: int = 128)\
        -> dict:
    """Batched placement sweep vs per-package build() loop (PR 2 tentpole).

    Both paths run in float64: the batched path is one ``build_family``
    (symbolic assembly + template Cholesky) plus ONE device call over the
    (B, P) parameter batch; the loop is the pre-family workflow —
    instantiate + discretize + assemble + solve per candidate. The two
    must agree to <=1e-6 degC (recorded as ``match_max_err_degc``).
    """
    pkg, n_src, _ = _package(system)
    with x64():
        t0 = time.perf_counter()
        family = PackageFamily(pkg, params=("grid_offsets",))
        sim = build_family(family, "rc", dtype=jnp.float64)
        t_build = time.perf_counter() - t0
        params = family.sample_params(n_candidates, seed=0)
        q = np.full((n_candidates, n_src), 3.0)

        def batched():
            th = sim.steady_state_batch(params, q)
            return np.asarray(sim.observe_batch(th, params))

        t0 = time.perf_counter()
        temps = batched()                      # includes compile
        t_cold = time.perf_counter() - t0
        t_warm = _host_time(batched, reps=3)

        t0 = time.perf_counter()
        loop = np.empty_like(temps)
        for b in range(n_candidates):
            m = build(family.instantiate(params[b]), "rc",
                      dtype=jnp.float64)
            loop[b] = np.asarray(m.observe(m.steady_state(q[b])))
        t_loop = time.perf_counter() - t0

    out = {"system": system, "b": n_candidates,
           "n_params": family.n_params, "nodes": family.grid.n,
           "family_build_s": t_build,
           "batched_cold_s": t_cold, "batched_s": t_warm,
           "loop_s": t_loop,
           "per_candidate_batched_s": t_warm / n_candidates,
           "per_candidate_loop_s": t_loop / n_candidates,
           "speedup": t_loop / max(t_warm, 1e-12),
           "speedup_cold": t_loop / max(t_cold, 1e-12),
           "match_max_err_degc": float(np.abs(temps - loop).max()),
           "peak_best_degc": float(temps.max(axis=1).min()),
           "peak_worst_degc": float(temps.max(axis=1).max())}
    print(f"[dse_sweep] {system:8s} B={n_candidates:4d} "
          f"batched={t_warm:.3f}s (cold {t_cold:.2f}s) loop={t_loop:.2f}s "
          f"speedup={out['speedup']:.1f}x "
          f"match={out['match_max_err_degc']:.2e}C", flush=True)
    return out


def bench_dse_opt(system: str = "2p5d_16", sweep_b: int = 10000,
                  chunk: int = 512, n_starts: int = 6) -> dict:
    """Gradient DSE (ISSUE 10 proof): the multi-start implicit-adjoint
    optimizer vs the B-candidate random sweep at <=5% of its solves.

    Same family, workload and f64 numerics on both sides. The sweep pays
    ``sweep_b`` steady solves (chunk-streamed cg tier); the optimizer is
    ``optimize_family`` (projected Adam on the annealed smooth-max peak,
    gradients through the implicit-adjoint fused-CG path) capped at a
    ``budget`` of ``0.05 * sweep_b`` solve-equivalents — a gradient
    evaluation priced at 2 (forward + ONE adjoint solve). The analytic
    count is cross-checked against the adjoint stats registry, whose
    CGStats (iterations / residual / converged, with the standard
    ``warn_unconverged`` iteration-cap discipline) are recorded. A small
    ROM-rung transient-peak optimization runs end to end in the same
    section (reverse-differentiated r x r ZOH rollout).
    """
    from repro.core import optimize_family
    from repro.core.rc_model import RCFamilyModel
    from repro.kernels.fused_cg import adjoint

    pkg, n_src, _ = _package(system)
    with x64():
        family = PackageFamily(pkg, params=("grid_offsets",))
        model = RCFamilyModel(family, dtype=jnp.float64, solver="cg",
                              chunk_size=chunk)
        # a hot cluster: the workload placement gradients actually feel
        q = np.full(n_src, 0.4)
        hot = [5, 6, 9, 10] if n_src >= 16 else list(
            range(max(1, n_src // 4)))
        q[hot] = 3.0

        t0 = time.perf_counter()
        params = family.sample_params(sweep_b, seed=0)
        peaks = np.asarray(model.peak_steady(
            params, np.broadcast_to(q, (sweep_b, n_src))))
        t_sweep = time.perf_counter() - t0
        sweep_best = float(peaks.min())

        budget = int(0.05 * sweep_b)
        # trade starts for depth when the budget is tight (smoke): ~15+
        # Adam iterations per start matter more than a wide population
        n_starts = max(2, min(n_starts, budget // 32))
        # size the anneal to the budget so tau actually reaches tau1
        steps = max(1, (budget - 2 * n_starts) // (2 * n_starts))
        adjoint.reset_adjoint_stats()
        res = optimize_family(model, q, n_starts=n_starts, method="adam",
                              steps=steps, lr=0.1, tau=(2.0, 0.05),
                              budget=budget, seed=0)
        counts = adjoint.solve_counts()
        site = "rc family peak_steady adjoint CG"
        stats = adjoint.last_stats(site)
        adj_rows = counts.get(site, {}).get("rows", 0)
        adj_stats = {
            "adjoint_row_solves": adj_rows,
            "adjoint_iters_max": int(np.max(stats.iterations))
            if stats is not None else None,
            "adjoint_residual_max": float(np.max(stats.residual))
            if stats is not None else None,
            "adjoint_converged": bool(np.all(stats.converged))
            if stats is not None else None,
        }

        # ROM-rung transient objective end to end (whole-trace peak)
        rom = build_family(family, "rom", dtype=jnp.float64)
        t_traj = 20
        qt = np.tile(q, (t_traj, 1)) * np.linspace(
            0.5, 1.5, t_traj)[:, None]
        t0 = time.perf_counter()
        res_t = optimize_family(rom, objective="peak_transient",
                                q_traj=qt, dt=0.01, n_starts=4, steps=10,
                                budget=200, seed=0)
        t_rom = time.perf_counter() - t0

    out = {"system": system, "nodes": family.grid.n,
           "n_params": family.n_params,
           "sweep_b": sweep_b, "sweep_best_degc": sweep_best,
           "sweep_s": t_sweep,
           "opt_best_degc": res.best_value,
           "opt_method": res.method, "opt_iters": res.n_iters,
           "opt_evals": res.n_evals,
           "opt_solve_equiv": res.n_solve_equiv,
           "opt_budget": budget,
           "opt_s": res.wall_s,
           "solve_frac_of_sweep": res.n_solve_equiv / sweep_b,
           "beats_sweep": bool(res.best_value <= sweep_best),
           **adj_stats,
           "rom_transient": {"t_steps": t_traj,
                             "best_degc": res_t.best_value,
                             "solve_equiv": res_t.n_solve_equiv,
                             "wall_s": t_rom}}
    print(f"[dse_opt  ] {system:8s} sweep B={sweep_b} "
          f"best={sweep_best:.3f}C ({t_sweep:.1f}s) | opt "
          f"best={res.best_value:.3f}C solves={res.n_solve_equiv} "
          f"({100 * out['solve_frac_of_sweep']:.1f}% of sweep, "
          f"{res.wall_s:.1f}s) beats_sweep={out['beats_sweep']} | "
          f"adjoint rows={adj_rows} "
          f"iters<={adj_stats['adjoint_iters_max']}", flush=True)
    return out


def bench_sparse_solver(system: str, n_steps: int = 50) -> dict:
    """Solver tier (PR 3): dense Cholesky/solve vs the matrix-free CG
    path built on the ``kernels/coo_matvec`` segment-sum kernel.

    Per system: warm steady-solve time on both tiers, per-step transient
    time (prefactored BE vs matrix-free BE-CG) including the dense tier's
    one-time factorization, and the f32 steady agreement between tiers.
    The scaling story is the point: past a couple thousand nodes the
    dense O(N^3) factor/solve loses to O(E * iters), which is what
    ``solver="auto"`` keys on (``fidelity.SOLVER_CROSSOVER_NODES``).
    """
    pkg, n_src, spec = _package(system)
    dt = 0.01
    q = np.full(n_src, 3.0, np.float32)
    q_traj = wl1(n_src, dt=dt, spec=spec)[:n_steps].astype(np.float32)

    out = {"system": system, "n_steps": n_steps}
    models = {}
    for tier in ("dense", "cg"):
        def _build(tier=tier):
            models[tier] = build(pkg, "rc", solver=tier)
        out[f"build_{tier}_s"] = _host_time(_build, reps=1)
        m = models[tier]
        out["nodes"] = m.net.n
        out["edges"] = int(m.net.rows.size)
        out[f"steady_{tier}_s"] = _time(
            lambda m=m: m.observe(m.steady_state(q)))
        if tier == "cg":
            st = m.last_cg_stats
            out["steady_cg_iters"] = int(np.asarray(st.iterations).max())
            out["steady_cg_residual"] = float(
                np.asarray(st.residual).max())
            out["steady_cg_converged"] = bool(
                np.asarray(st.converged).all())
        t0 = time.perf_counter()
        sim = m.make_simulator(dt)
        jax.block_until_ready(sim(m.zero_state(), q_traj))  # compile+factor
        out[f"transient_cold_{tier}_s"] = time.perf_counter() - t0
        t = _time(lambda: sim(m.zero_state(), q_traj), warmup=0, reps=2)
        out[f"per_step_{tier}_s"] = t / n_steps
    t_d = np.asarray(models["dense"].observe(
        models["dense"].steady_state(q)))
    t_c = np.asarray(models["cg"].observe(models["cg"].steady_state(q)))
    out["steady_match_f32_degc"] = float(np.abs(t_d - t_c).max())
    out["steady_speedup_cg"] = out["steady_dense_s"] \
        / max(out["steady_cg_s"], 1e-12)
    print(f"[sparse   ] {system:9s} n={out['nodes']:5d} "
          f"dense={out['steady_dense_s']*1e3:8.2f}ms "
          f"cg={out['steady_cg_s']*1e3:7.2f}ms "
          f"speedup={out['steady_speedup_cg']:6.2f}x "
          f"match={out['steady_match_f32_degc']:.1e}C", flush=True)
    return out


def bench_fused_cg(system: str, n_steps: int = 50) -> dict:
    """Fused CG solve (one kernel launch per CG iteration; on CPU the
    ELL ``pcg_loop``) vs the dense tier, steady and transient, with the
    per-solve stats the solver reports. Per-iteration time divides the
    end-to-end solve by the reported iteration count."""
    pkg, n_src, spec = _package(system)
    dt = 0.01
    q = np.full(n_src, 3.0, np.float32)
    q_traj = wl1(n_src, dt=dt, spec=spec)[:n_steps].astype(np.float32)

    dense = build(pkg, "rc", solver="dense")
    out = {"system": system, "n_steps": n_steps, "nodes": dense.net.n,
           "edges": int(dense.net.rows.size)}
    out["steady_dense_s"] = _time(
        lambda: dense.observe(dense.steady_state(q)))
    sim_d = dense.make_simulator(dt)
    out["per_step_dense_s"] = _time(
        lambda: sim_d(dense.zero_state(), q_traj), warmup=1, reps=2) \
        / n_steps

    m = build(pkg, "rc", solver="cg")
    out["steady_fused_s"] = _time(lambda: m.observe(m.steady_state(q)))
    st = m.last_cg_stats
    iters = int(np.asarray(st.iterations).max())
    out["steady_fused_iters"] = iters
    out["steady_fused_residual"] = float(np.asarray(st.residual).max())
    out["steady_fused_converged"] = bool(np.asarray(st.converged).all())
    out["steady_per_iter_fused_us"] = \
        out["steady_fused_s"] / max(iters, 1) * 1e6
    sim = m.make_simulator(dt)
    out["per_step_fused_s"] = _time(lambda: sim(m.zero_state(), q_traj),
                                    warmup=1, reps=2) / n_steps
    stt = getattr(sim, "last_stats", None)
    step_iters = float(np.asarray(stt.iterations).mean()) \
        if stt is not None else float("nan")
    out["transient_iters_per_step_fused"] = step_iters
    out["transient_per_iter_fused_us"] = \
        out["per_step_fused_s"] / max(step_iters, 1e-12) * 1e6

    out["steady_speedup_cg"] = out["steady_dense_s"] \
        / max(out["steady_fused_s"], 1e-12)  # key _steady_crossover_nodes
    print(f"[fused_cg ] {system:9s} n={out['nodes']:5d} "
          f"steady fused={out['steady_fused_s']*1e3:7.2f}ms "
          f"dense={out['steady_dense_s']*1e3:7.2f}ms "
          f"iters={out['steady_fused_iters']:4d} "
          f"per_iter={out['steady_per_iter_fused_us']:6.1f}us", flush=True)
    return out


def bench_rom(system: str, n_steps: int = 400) -> dict:
    """ROM rung (PR 4): Krylov moment-matching projection vs the dense
    RC tier and the full-order DSS.

    Per system: one-time basis-construction cost, reduction ratio N/r,
    warm per-step transient time on the WL1 trace for the reduced model
    vs the dense prefactored-BE tier (the headline: per-step cost
    independent of node count), and the max observation error of the ROM
    rollout against the full-order exact-ZOH (DSS) response evaluated in
    float64 on the host — so the error metric reports basis truncation,
    not f32 rollout noise.
    """
    pkg, n_src, spec = _package(system)
    dt = 0.01
    q = np.full(n_src, 3.0, np.float32)
    q_traj = wl1(n_src, dt=dt, spec=spec)[:n_steps]

    rc = build(pkg, "rc", solver="dense")
    sim_rc = rc.make_simulator(dt)
    t = _time(lambda: sim_rc(rc.zero_state(), q_traj.astype(np.float32)),
              warmup=1, reps=2)
    out = {"system": system, "n_steps": n_steps, "nodes": rc.net.n,
           "per_step_dense_s": t / n_steps}

    models = {}

    def _build():
        models["rom"] = build(pkg, "rom", ts=dt)
    out["build_rom_s"] = _host_time(_build, reps=1)
    rom = models["rom"]
    out["r"] = rom.r
    out["reduction_ratio"] = rom.reduction_ratio
    sim_rom = rom.make_simulator(dt)
    t = _time(lambda: sim_rom(rom.zero_state(),
                              q_traj.astype(np.float32)))
    out["per_step_rom_s"] = t / n_steps
    out["transient_speedup_vs_dense"] = out["per_step_dense_s"] \
        / max(out["per_step_rom_s"], 1e-12)
    out["steady_rom_s"] = _time(
        lambda: rom.observe(rom.steady_state(q)))

    # full-order exact-ZOH reference AND the reduced rollout, both in
    # float64 on the host, so the error metric isolates basis truncation
    # (the timed f32 rollout above would otherwise fold its own ~1e-3 C
    # accumulation noise into the number)
    css = continuous_ss(rc)
    ad, bd = zoh_discretize(css.a, css.b_src, dt)
    ad_r, bd_r = zoh_discretize(rom._a, rom._b, dt)
    theta = np.zeros(rc.net.n)
    th_r = np.zeros(rom.r)
    err = 0.0
    for k in range(n_steps):
        theta = ad @ theta + bd @ q_traj[k]
        th_r = ad_r @ th_r + bd_r @ q_traj[k]
        err = max(err, np.abs(rom.hhat @ th_r - css.h @ theta).max())
    out["max_obs_err_vs_dss_degc"] = float(err)
    print(f"[rom      ] {system:9s} n={out['nodes']:5d} r={rom.r:4d} "
          f"({out['reduction_ratio']:5.1f}x smaller) "
          f"per_step={out['per_step_rom_s']*1e6:7.1f}us "
          f"({out['transient_speedup_vs_dense']:6.0f}x vs dense) "
          f"err={out['max_obs_err_vs_dss_degc']:.3f}C "
          f"build={out['build_rom_s']:.1f}s", flush=True)
    return out


def _rss_mb() -> float:
    """Process peak RSS so far (ru_maxrss is KiB on Linux)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _run_sharded_cfg(cfg: dict) -> dict:
    """One sharded_dse config in THIS process: the sweep runs on a mesh
    over the first ``cfg["devices"]`` devices (``make_host_mesh`` builds
    submeshes), so one process holds the chip(s) for every config.

    ``sweep_rss_mb`` is how far the sweep raised the process's peak RSS;
    ``bench_sharded_dse`` runs the streamed configs before the unchunked
    baseline so each is measured against the setup's high-water mark.
    """
    fam = PackageFamily(make_2p5d_package(cfg["chips"]),
                        params=("grid_offsets",))
    # draw candidates in slices: sample_params validates host-side with
    # (B, E)-sized temporaries, and one B=10k draw would dominate the
    # high-water mark meant to expose the SWEEP's footprint
    slice_b = min(cfg["b"], 1000)
    params = np.vstack([fam.sample_params(min(slice_b, cfg["b"] - s),
                                          seed=s)
                        for s in range(0, cfg["b"], slice_b)])
    q = np.full((cfg["b"], cfg["chips"]), 3.0, np.float32)
    sim = build_family(fam, "rc",
                       mesh=cfg["devices"] if cfg["devices"] > 1 else None,
                       chunk_size=cfg["chunk"])

    def sweep():
        th = sim.steady_state_batch(params, q)
        return np.asarray(sim.observe_batch(th, params))

    setup_rss = _rss_mb()
    t0 = time.perf_counter()
    temps = sweep()
    cold = time.perf_counter() - t0
    warm = _host_time(sweep, reps=cfg["reps"])
    return {"devices": cfg["devices"], "b": cfg["b"], "chunk": cfg["chunk"],
            "cold_s": cold, "warm_s": warm,
            "per_candidate_us": warm / cfg["b"] * 1e6,
            "peak_temp_degc": float(temps.max()),
            "setup_rss_mb": setup_rss, "peak_rss_mb": _rss_mb(),
            "sweep_rss_mb": _rss_mb() - setup_rss}


def bench_sharded_dse(system: str = "2p5d_16", b_scale: int = 2048,
                      b_stream: int = 10000, chunk: int = 512,
                      device_counts=(1, 2, 4, 8), reps: int = 3) -> dict:
    """Sharded family execution (PR 5 tentpole): the RC steady sweep over
    a mesh of devices and through the chunk-streamed path.

    Every config runs in this process, on the device counts in
    ``device_counts`` that the process has (on the CPU, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before
    starting it to get simulated devices). Two sub-sections:
    ``scaling`` sweeps the device count at a fixed mid-size B (speedup
    vs the single-device vmap path); ``streamed`` runs the large-B sweep
    (default 10k candidates) through ``chunk_size`` streaming, on one
    device and on the largest mesh, plus the same B unchunked as the
    memory baseline (run last: ``sweep_rss_mb`` is high-water growth).
    """
    _, chips, _ = _package(system)  # 2.5D: one source per chiplet
    counts = [d for d in device_counts if d <= len(jax.devices())]
    scaling = []
    for d in counts:
        r = _run_sharded_cfg({"devices": d, "b": b_scale, "chunk": None,
                              "chips": chips, "reps": reps})
        scaling.append(r)
        print(f"[sharded  ] {system:8s} B={b_scale:5d} devices={d} "
              f"warm={r['warm_s']:.3f}s rss={r['peak_rss_mb']:.0f}MB",
              flush=True)
    base = scaling[0]["warm_s"]
    for r in scaling:
        r["speedup_vs_1dev"] = base / max(r["warm_s"], 1e-12)

    streamed = []
    stream_cfgs = [
        {"devices": 1, "chunk": chunk},
        {"devices": max(counts), "chunk": chunk},
        {"devices": 1, "chunk": None},                 # vmap mem baseline
    ]
    for c in stream_cfgs:
        r = _run_sharded_cfg({**c, "b": b_stream, "chips": chips,
                              "reps": max(1, reps - 1)})
        streamed.append(r)
        print(f"[sharded  ] {system:8s} B={b_stream:5d} "
              f"devices={r['devices']} chunk={r['chunk']} "
              f"warm={r['warm_s']:.2f}s "
              f"sweep_rss={r['sweep_rss_mb']:.0f}MB",
              flush=True)
    vmap_base = streamed[-1]["warm_s"]
    for r in streamed:
        r["speedup_vs_1dev_vmap"] = vmap_base / max(r["warm_s"], 1e-12)
    return {"system": system, "b_scale": b_scale, "b_stream": b_stream,
            "chunk": chunk, "scaling": scaling, "streamed": streamed}


def _steady_crossover_nodes(rows: list) -> float:
    """Dense-vs-CG steady crossover in nodes, log-log interpolated
    between the neighboring measured systems (inf if CG never wins)."""
    rows = sorted(rows, key=lambda r: r["nodes"])
    for lo, hi in zip(rows, rows[1:]):
        s0, s1 = lo["steady_speedup_cg"], hi["steady_speedup_cg"]
        if s0 < 1.0 <= s1:
            f = np.log(1.0 / s0) / np.log(s1 / s0)
            return float(np.exp(np.log(lo["nodes"]) * (1 - f)
                                + np.log(hi["nodes"]) * f))
    if rows and rows[0]["steady_speedup_cg"] >= 1.0:
        return float(rows[0]["nodes"])
    return float("inf")


def _check_crossover_calibration(measured: float) -> dict:
    """Compare the measured dense-vs-CG steady crossover against the
    ``solver="auto"`` constant and warn when the constant has drifted
    more than 2x from what this container actually measures."""
    const = SOLVER_CROSSOVER_NODES
    if not (np.isfinite(measured) and measured > 0):
        # CG never won on the measured ladder: the maximal drift — any
        # finite constant routes large systems onto the losing tier
        print(f"[sparse   ] WARNING: CG never beat dense on the measured "
              f"ladder (crossover={measured}); solver='auto' with "
              f"SOLVER_CROSSOVER_NODES={const} would still pick CG at "
              f">={const} nodes — recalibrate the constant in "
              f"core/fidelity.py", flush=True)
        return {"constant": const, "calibration_ok": False}
    ratio = max(const / measured, measured / const)
    ok = ratio <= 2.0
    if not ok:
        print(f"[sparse   ] WARNING: SOLVER_CROSSOVER_NODES={const} "
              f"is {ratio:.1f}x off the measured steady crossover "
              f"(~{measured:.0f} nodes) — recalibrate the constant "
              f"in core/fidelity.py", flush=True)
    return {"constant": const, "calibration_ok": bool(ok)}


def bench_serving(system: str = "2p5d_16", n_requests: int = 200,
                  t_steps: int = 50, storm: int = 64) -> dict:
    """The thermal-oracle serving section (PR 7): cold-vs-warm build,
    warmed sequential p50/p99 per request kind, threaded-storm
    throughput and batch occupancy — the headline is sub-ms p50 steady
    and ROM-transient answers against a warm content-addressed cache."""
    import threading

    from repro.serving import ThermalOracle

    pkg, n_src, _ = _package(system)
    oracle = ThermalOracle(fidelity="rom", capacity=8,
                           max_queue=4 * storm)
    _, hit_cold, cold_build_s = oracle.warm(pkg)
    # a structurally identical, independently constructed geometry must
    # be a pure cache hit — "warm build time" is just the key hash+lookup
    pkg_again = _package(system)[0]
    warm_lookup_s = _host_time(lambda: oracle.warm(pkg_again), reps=5)
    assert oracle.warm(pkg_again)[1] is True

    q = np.full(n_src, 3.0)
    q_traj = np.full((t_steps, n_src), 2.0)
    oracle.query_steady(pkg, q)                  # compile/warm the
    oracle.query_transient(pkg, q_traj, 0.01)    # serving executables

    def _lat(fn, n):
        lats = []
        for _ in range(n):
            resp = fn()
            assert resp.ok, resp.detail
            lats.append(resp.latency_s)
        arr = np.asarray(lats)
        return {"n": n, "p50_s": float(np.percentile(arr, 50)),
                "p99_s": float(np.percentile(arr, 99)),
                "mean_s": float(arr.mean())}

    lat_steady = _lat(lambda: oracle.query_steady(pkg, q), n_requests)
    lat_tran = _lat(lambda: oracle.query_transient(pkg, q_traj, 0.01),
                    max(n_requests // 4, 10))

    # threaded storm: concurrent clients drive batching; throughput and
    # occupancy are the continuous-batching payoff
    responses = [None] * storm

    def client(i):
        responses[i] = oracle.query_steady(pkg, q)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(storm)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    assert all(r.ok for r in responses)
    occ = float(np.mean([r.occupancy for r in responses]))
    snap = oracle.telemetry.snapshot()
    oracle.close()
    out = {"system": system, "nodes_srcs": n_src, "capacity": 8,
           "cold_build_s": cold_build_s,
           "warm_lookup_s": warm_lookup_s,
           "warm_speedup": cold_build_s / max(warm_lookup_s, 1e-9),
           "steady": lat_steady,
           "rom_transient": {"t_steps": t_steps, **lat_tran},
           "storm": {"clients": storm, "wall_s": wall,
                     "req_per_s": storm / wall,
                     "mean_batch_occupancy": occ},
           "cache": snap["cache"],
           "by_status": snap["by_status"]}
    print(f"[serving  ] {system}: cold build {cold_build_s:.2f}s -> "
          f"warm lookup {warm_lookup_s*1e6:.0f}us "
          f"({out['warm_speedup']:.0f}x); steady p50 "
          f"{lat_steady['p50_s']*1e3:.2f}ms p99 "
          f"{lat_steady['p99_s']*1e3:.2f}ms; rom-transient[{t_steps}] "
          f"p50 {lat_tran['p50_s']*1e3:.2f}ms; storm {storm} clients "
          f"{out['storm']['req_per_s']:.0f} req/s occ {occ:.2f}",
          flush=True)
    return out


def _router_reference(net, q_steady, q_traj, dt):
    """Independent full-order f64 answers for the router section: scipy
    LU for steady, exact ZOH of the WHITENED symmetric pencil via scipy
    Pade expm for the transient — different algorithms than any rung the
    router answers from (Cholesky / eigh), same f64 network. (Mirrors
    tests/test_router.py; the ladder's own ``"dss"`` rung exponentiates
    the unsymmetrized stiff ``C^-1 G``, whose Pade error ~1e-4 per unit
    drive would dominate the measurement.)"""
    import scipy.linalg as sla

    from repro.core import observation_matrix
    h = observation_matrix(net, sorted({t for t in net.grid.tags if t}))
    p = np.asarray(net.P, np.float64)
    neg_g = -net.g_dense()
    steady = h @ sla.lu_solve(sla.lu_factor(neg_g), p @ q_steady) \
        + net.t_ambient
    ci = 1.0 / np.sqrt(np.asarray(net.C, np.float64))
    sym = -neg_g * ci[:, None] * ci
    ad_w = sla.expm(sym * dt)
    bd_w = sla.solve(sym, (ad_w - np.eye(net.n)) @ (ci[:, None] * p),
                     assume_a="sym")
    z = np.zeros(net.n)
    obs = np.empty((q_traj.shape[0], h.shape[0]))
    for k in range(q_traj.shape[0]):
        z = ad_w @ z + bd_w @ q_traj[k]
        obs[k] = h @ (ci * z) + net.t_ambient
    return steady, obs


def bench_router(system: str, t_steps: int = 60,
                 tols=(1e-1, 1e-2, 1e-3)) -> dict:
    """The adaptive-router section (ISSUE 8): chosen rung, certified vs
    measured error and routing overhead per (system, tol). The WL1
    drive is amplitude-normalized so the ROM certificate sits at ~8e-3
    (the certificate is linear in the drive): the sweep then exercises
    both regimes — certify-on-the-cheap-rung at loose tol, escalate to
    the reference rung at tight tol — on every system."""
    pkg, n_src, _ = _package(system)
    t0 = time.perf_counter()
    router = build(pkg, "auto", tol=1e-2, ts=0.01)
    router.certifier.reference()            # include the eigh reference
    build_s = time.perf_counter() - t0      # in the quoted build cost
    q_steady = np.full(n_src, 3.0)
    q_unit = wl1(n_src, dt=0.01)[:t_steps].astype(np.float64)
    cert0 = router.query_transient(q_unit, rung="rom").certified
    q_traj = q_unit * (8e-3 / cert0)
    ref_steady, ref_traj = _router_reference(router.net, q_steady,
                                             q_traj, 0.01)
    rows = []
    for kind, run, ref in (
            ("steady", lambda t: router.query_steady(q_steady, tol=t),
             ref_steady),
            ("transient", lambda t: router.query_transient(q_traj, tol=t),
             ref_traj)):
        for tol in tols:
            ans = run(tol)
            measured = float(np.abs(ans.value - ref).max())
            assert ans.certified >= measured, \
                (system, kind, tol, ans.certified, measured)
            rows.append({"kind": kind, "tol": tol, "rung": ans.rung,
                         "certified_degc": ans.certified,
                         "measured_degc": measured,
                         "escalations": ans.escalations,
                         "overhead_s": ans.overhead_s})
    # loose-vs-tight differentiation is part of the record
    t_rungs = {r["tol"]: r["rung"] for r in rows
               if r["kind"] == "transient"}
    assert t_rungs[1e-1] == "rom" and t_rungs[1e-3] == "dss", t_rungs
    out = {"system": system, "nodes": router.n, "build_s": build_s,
           "t_steps": t_steps, "rows": rows}
    worst = max(r["certified_degc"] / max(r["measured_degc"], 1e-300)
                for r in rows)
    print(f"[router   ] {system}: n={router.n} build {build_s:.2f}s; "
          f"transient rungs {t_rungs[1e-1]}@1e-1 -> {t_rungs[1e-3]}@1e-3"
          f"; cert>=meas on {len(rows)} rows (loosest x{worst:.0e})",
          flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="smallest system, short trace, small sweep (CI)")
    ap.add_argument("--dse-b", type=int, default=None,
                    help="candidate count for the dse_sweep section")
    ap.add_argument("--out", default="BENCH_exec_time.json")
    args = ap.parse_args(argv)
    use_compile_cache()
    if args.smoke:
        sim_systems, n_steps = ["2p5d_16"], 200
        assembly_systems = ["2p5d_16"]
        # keep one >=4k-node point so the artifact always shows the
        # dense-vs-CG gap at scale
        sparse_systems = ["2p5d_16", "2p5d_256"]
        # the ROM section stays on the small system in CI (the 256-chip
        # reference needs an N x N host expm — default/full runs only)
        rom_systems, rom_steps = ["2p5d_16"], 200
        dse_b = args.dse_b or 32
        dse_opt_kw = dict(sweep_b=2000, chunk=512)
        sharded_kw = dict(b_scale=256, b_stream=1024, chunk=256, reps=2)
        serving_kw = dict(n_requests=50, storm=32)
    else:
        sim_systems = ["2p5d_16", "2p5d_36", "2p5d_64", "3d_16x3"] \
            if args.full else ["2p5d_16", "3d_16x3"]
        n_steps = 4000 if args.full else 600
        # assembly speedup is always tracked on the paper's largest systems
        assembly_systems = ["2p5d_16", "2p5d_64", "3d_16x3"]
        # the solver-tier scaling ladder: Table-6 sizes plus the
        # beyond-the-paper 256-chiplet 2.5D and 16x6-stack 3D systems
        sparse_systems = ["2p5d_16", "2p5d_64", "3d_16x6", "2p5d_256"]
        # ROM headline: per-step cost independent of N, incl. the
        # 8196-node system where the dense tier pays ~56 ms/step
        rom_systems = ["2p5d_16", "2p5d_64", "3d_16x6", "2p5d_256"]
        rom_steps = 400
        dse_b = args.dse_b or 128
        dse_opt_kw = dict(sweep_b=10000, chunk=512)
        sharded_kw = dict(b_scale=2048, b_stream=10000, chunk=512, reps=3)
        serving_kw = dict(n_requests=200, storm=64)
    assembly = [bench_assembly(s) for s in assembly_systems]
    systems = [run_system(s, n_steps) for s in sim_systems]
    sparse = [bench_sparse_solver(s) for s in sparse_systems]
    crossover = _steady_crossover_nodes(sparse)
    print(f"[sparse   ] steady dense-vs-CG crossover ~ {crossover:.0f} "
          f"nodes", flush=True)
    fused = [bench_fused_cg(s) for s in sparse_systems]
    fused_crossover = _steady_crossover_nodes(fused)
    print(f"[fused_cg ] steady dense-vs-fused-CG crossover ~ "
          f"{fused_crossover:.0f} nodes", flush=True)
    # the 2x drift warning needs the full ladder: smoke's two-point
    # (564/8196) interpolation is biased low, so don't raise false
    # alarms from CI smoke runs
    calibration = _check_crossover_calibration(crossover) \
        if not args.smoke else {"constant": SOLVER_CROSSOVER_NODES,
                                "calibration_ok": None}
    rom = [bench_rom(s, n_steps=rom_steps) for s in rom_systems]
    sharded = bench_sharded_dse("2p5d_16", **sharded_kw)
    serving = bench_serving("2p5d_16", **serving_kw)
    # the router section always covers the full Table-6 ladder (the CI
    # certified>=measured assertion is per system, smoke included)
    router = [bench_router(s)
              for s in ["2p5d_16", "2p5d_36", "2p5d_64", "3d_16x3"]]
    # last: the sweeps run (and trace) under x64
    dse = [bench_dse_sweep("2p5d_16", n_candidates=dse_b)]
    dse_opt = [bench_dse_opt("2p5d_16", **dse_opt_kw)]
    results = {"bench": "exec_time", "full": bool(args.full),
               "smoke": bool(args.smoke),
               "assembly": assembly, "systems": systems,
               "sparse_solver": {"systems": sparse,
                                 "steady_crossover_nodes": crossover,
                                 **calibration},
               "fused_cg": {"systems": fused,
                            "steady_crossover_nodes": fused_crossover},
               "rom": rom,
               "sharded_dse": sharded,
               "serving": serving,
               "router": router,
               "dse_sweep": dse,
               "dse_opt": dse_opt}
    if os.path.dirname(args.out):
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    for r in systems:
        for m, t in r["times"].items():
            print(f"fig8,{r['system']},{m},{t*1e6:.1f}us_total")
    for a in assembly:
        print(f"assembly,{a['system']},speedup,"
              f"{a['assembly_speedup']:.1f}x")
    for s in sparse:
        print(f"sparse,{s['system']},n{s['nodes']},steady_speedup,"
              f"{s['steady_speedup_cg']:.2f}x")
    for s in fused:
        print(f"fused_cg,{s['system']},n{s['nodes']},vs_dense,"
              f"{s['steady_speedup_cg']:.2f}x,iters,"
              f"{s['steady_fused_iters']}")
    for s in rom:
        print(f"rom,{s['system']},r{s['r']},per_step_speedup,"
              f"{s['transient_speedup_vs_dense']:.0f}x,err,"
              f"{s['max_obs_err_vs_dss_degc']:.3f}C")
    for d in dse:
        print(f"dse,{d['system']},B{d['b']},speedup,{d['speedup']:.1f}x")
    for d in dse_opt:
        print(f"dse_opt,{d['system']},sweepB{d['sweep_b']},"
              f"sweep_best,{d['sweep_best_degc']:.3f}C,opt_best,"
              f"{d['opt_best_degc']:.3f}C,solves,{d['opt_solve_equiv']},"
              f"frac,{d['solve_frac_of_sweep']:.3f},beats_sweep,"
              f"{d['beats_sweep']}")
    for r in sharded["scaling"]:
        print(f"sharded,{sharded['system']},B{r['b']},dev{r['devices']},"
              f"speedup,{r['speedup_vs_1dev']:.2f}x")
    for r in sharded["streamed"]:
        print(f"sharded,{sharded['system']},B{r['b']},dev{r['devices']},"
              f"chunk{r['chunk']},sweep_rss,{r['sweep_rss_mb']:.0f}MB")
    for r in router:
        for row in r["rows"]:
            print(f"router,{r['system']},{row['kind']},tol{row['tol']:g},"
                  f"rung,{row['rung']},cert,{row['certified_degc']:.2e},"
                  f"meas,{row['measured_degc']:.2e}")
    print(f"serving,{serving['system']},steady_p50,"
          f"{serving['steady']['p50_s']*1e6:.0f}us,transient_p50,"
          f"{serving['rom_transient']['p50_s']*1e6:.0f}us,throughput,"
          f"{serving['storm']['req_per_s']:.0f}req/s,occupancy,"
          f"{serving['storm']['mean_batch_occupancy']:.2f},warm_speedup,"
          f"{serving['warm_speedup']:.0f}x")
    return results


if __name__ == "__main__":
    main()
