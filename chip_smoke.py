#!/usr/bin/env python3
"""Bring-up smoke test: drive the thermal system's main path on a TPU.

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --chips 4     # four chips: the sharded sweep only

Everything runs in this one process, in f32 on the device, through the
entry points users call (``repro.core.build`` / ``build_family`` and
``repro.serving.ThermalOracle``), at the paper's Table-6 sizes. Inputs
are drawn from ``--seed``. Each phase prints one JSON line with its
result; every error is checked against the bar the CPU tests already
set for that path in f32, and is printed beside it. The phases:

  preflight  JAX version and devices; fails unless the backend is a TPU.
  steady     refined f32 CG steady solves of 2p5d_64 and 3d_16x3 against
             a host f64 sparse solve, and proof that the compiled solve
             holds the fused-CG Pallas kernel (``fused_cg_step``).
  oracle     a ThermalOracle warmed on 2p5d_64 answers concurrent
             client threads: ROM steady and transient, DSS transient
             (the ``dss_step`` kernel), DTPM control traces, certified
             ``auto`` steady, and one family-steady request.
  sweep      a B = 10,000 candidate 2p5d_64 placement x htc sweep,
             chunk-streamed, 64 candidates checked against host f64.
  sharded    (``--chips 4`` only) the same sweep on a 4-chip mesh against
             one chip, and the shards of a chunk on 4 distinct devices.

Any failure raises and the script exits non-zero; only a full pass
prints the last line, ``{"ok": true, "device": {...}}``. Latencies and
wall times are printed as set-up information, not as results.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Error bars in degC, each the bar a CPU test sets for the same path in
# f32 (the test named beside it). They are not widened for the chip.
BARS = {
    # tests/test_rc_fvm.py::test_refined_f32_cg_matches_f64_dense
    "refined_steady": 1e-6,
    # tests/test_rom.py::test_rom_tracks_dss_table6
    "rom": 0.1,
    # tests/test_dss.py::test_dss_batched_matches_single
    "dss_f32": 2e-2,
    # tests/test_family.py (f32 CG tolerance class)
    "family_f32_cg": 2e-3,
}
DT = 0.01
SWEEP_PARAMS = ("grid_offsets", "htc_top")


class SmokeFailure(AssertionError):
    """A phase's result missed its bar or its contract."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=float), flush=True)


# ---------------------------------------------------------------------------
# host f64 references (numpy/scipy only)
# ---------------------------------------------------------------------------
def host_network(pkg):
    """The network every registry rung builds for ``pkg`` (tuned caps)."""
    from repro.core.calibrate import default_cap_multipliers
    from repro.core.rc_model import build_network
    return build_network(pkg, cap_multipliers=default_cap_multipliers(pkg))


def host_steady_obs(net, q, tags) -> np.ndarray:
    """Observed steady temps from a host f64 sparse direct solve."""
    from scipy.sparse.linalg import spsolve
    from repro.core.rc_model import observation_matrix
    x = spsolve(net.neg_g_sparse(), net.P @ np.asarray(q, np.float64))
    return observation_matrix(net, tags) @ x + net.t_ambient


def host_dtpm_tmax(ad, bd, h, t_amb, powers, throttle) -> np.ndarray:
    """Max-temp trace of the DTPM plant replayed in f64 under the
    throttle sequence the device controller chose."""
    theta = np.zeros(ad.shape[0])
    out = np.empty(powers.shape[0])
    for k in range(powers.shape[0]):
        theta = ad @ theta + bd @ (powers[k] * throttle[k] ** 2.5)
        out[k] = (h @ theta).max() + t_amb
    return out


def wl1_row(n_src: int, rng, spec) -> np.ndarray:
    """One power vector from the PRBS phase of the WL1 trace."""
    from repro.core.workloads import wl1
    trace = wl1(n_src, dt=DT, spec=spec, seed=int(rng.integers(1 << 16)))
    lo = int(round(8.0 / DT))                 # end of the stress phase
    return trace[int(rng.integers(lo, lo + int(round(20.0 / DT))))]


def max_err(a, b) -> float:
    return float(np.abs(np.asarray(a, np.float64)
                        - np.asarray(b, np.float64)).max())


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_preflight() -> dict:
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    report("preflight", jax=jax.__version__, backend=jax.default_backend(),
           devices=[str(d) for d in devs], **device)
    check(jax.default_backend() == "tpu",
          f"no TPU: JAX backend is {jax.default_backend()!r}")
    return device


def phase_steady(rng, systems=("2p5d_64", "3d_16x3"),
                 expect_kernel: bool = True) -> None:
    import jax.numpy as jnp
    from repro.core import build, package_from_name
    from repro.core.workloads import P2P5D, P3D
    for name in systems:
        pkg, n_src = package_from_name(name)
        model = build(pkg, "rc", solver="cg")
        q = wl1_row(n_src, rng, P3D if name.startswith("3d") else P2P5D)
        t0 = time.perf_counter()
        temps = model.observe(model.steady_state(q))
        wall = time.perf_counter() - t0
        stats = model.last_cg_stats
        err = max_err(temps, host_steady_obs(model.net, q, model.tags))
        text = model._steady_fn.device_solve.lower(
            jnp.zeros(model.net.n, model.dtype)).compile().as_text()
        kernel = "tpu_custom_call" in text and "fused_cg_step" in text
        report("steady", system=name, nodes=model.net.n,
               max_err_c=err, bar_c=BARS["refined_steady"],
               cg_iterations=int(stats.iterations),
               converged=bool(stats.converged),
               fused_cg_kernel_in_program=kernel, setup_wall_s=wall)
        check(err <= BARS["refined_steady"],
              f"{name} steady error {err:.3e} C over its bar")
        check(bool(stats.converged), f"{name} refined steady unconverged")
        if expect_kernel:
            check(kernel, f"{name}: compiled CG solve has no "
                          "tpu_custom_call for fused_cg_step")


def phase_oracle(rng, system: str = "2p5d_64", n_steady: int = 12,
                 n_transient: int = 10, n_dss: int = 6, n_dtpm: int = 6,
                 n_auto: int = 4, t_steps: int = 256,
                 clients: int = 8) -> None:
    from repro.core import PackageFamily, package_from_name
    from repro.core.dss import EighZOH
    from repro.core.workloads import P2P5D, get_workload
    from repro.serving import ThermalOracle
    pkg, n_src = package_from_name(system)
    net = host_network(pkg)
    ref = EighZOH(net)
    family = PackageFamily(pkg, params=SWEEP_PARAMS)
    fam_opts = {"solver": "cg"}
    fam_p = family.sample_params(1, seed=int(rng.integers(1 << 16)))[0]

    def trace(i):  # a T-step window of one of WL2..WL6
        full = get_workload(f"WL{2 + i % 5}", n_src, dt=DT, spec=P2P5D,
                            seed=int(rng.integers(1 << 16)))
        start = int(rng.integers(0, max(1, full.shape[0] - t_steps)))
        win = full[start:start + t_steps]
        return np.pad(win, ((0, t_steps - win.shape[0]), (0, 0)))

    work = ([("steady", wl1_row(n_src, rng, P2P5D))
             for _ in range(n_steady)]
            + [("transient", trace(i)) for i in range(n_transient)]
            + [("dss_transient", trace(i)) for i in range(n_dss)]
            + [("dtpm", trace(i) * 1.6) for i in range(n_dtpm)]
            + [("auto_steady", wl1_row(n_src, rng, P2P5D))
               for _ in range(n_auto)]
            + [("family_steady", wl1_row(n_src, rng, P2P5D))])
    order = rng.permutation(len(work))

    oracle = ThermalOracle(capacity=8)       # default rung: "rom"
    try:
        t0 = time.perf_counter()
        oracle.warm(pkg)
        oracle.warm(pkg, fidelity="dss")
        auto_key, _, _ = oracle.warm(pkg, fidelity="auto")
        oracle.warm(family, fidelity="rc", **fam_opts)
        warm_s = time.perf_counter() - t0

        def submit(i):
            kind, x = work[i]
            if kind == "steady":
                return oracle.submit_steady(pkg, x)
            if kind == "transient":
                return oracle.submit_transient(pkg, x, DT)
            if kind == "dss_transient":
                return oracle.submit_transient(pkg, x, DT, fidelity="dss")
            if kind == "dtpm":
                return oracle.submit_dtpm(pkg, x, fidelity="dss")
            if kind == "auto_steady":
                return oracle.submit_steady(pkg, x, fidelity="auto")
            return oracle.submit_family_steady(family, fam_p, x,
                                               fidelity="rc", opts=fam_opts)

        with ThreadPoolExecutor(clients) as pool:
            pendings = list(pool.map(submit, order))
            responses = dict(zip(order, (p.result(timeout=900)
                                         for p in pendings)))
        router = oracle.cache.get(auto_key)
        breakers = router.breaker_states()
    finally:
        oracle.shutdown()

    ad, bd = ref.discretize(DT)
    errs, lat = {}, {}
    for i, (kind, x) in enumerate(work):
        resp = responses[i]
        check(resp.status == "ok",
              f"oracle {kind} request answered {resp.status!r}: "
              f"{resp.detail}")
        check(resp.fallback is None, f"oracle {kind} fell back: "
                                     f"{resp.fallback}")
        lat.setdefault(kind, []).append(resp.latency_s)
        if kind in ("steady", "auto_steady"):
            want = ref.h @ ref.steady(x) + ref.t_ambient
        elif kind in ("transient", "dss_transient"):
            want = ref.simulate(np.zeros(net.n), x, DT)
        elif kind == "dtpm":
            want = host_dtpm_tmax(ad, bd, ref.h, ref.t_ambient, x,
                                  np.asarray(resp.info["throttle_traj"]))
        else:
            want = host_steady_obs(
                host_network(family.instantiate(fam_p)), x, ref.tags)
        err = max_err(resp.value, want)
        errs[kind] = max(errs.get(kind, 0.0), err)
        if kind == "auto_steady":
            route = resp.route
            check(route is not None and route["certified_ok"]
                  and err <= route["certified"],
                  f"auto steady: measured {err:.3e} C vs route {route}")
    bars = {"steady": BARS["rom"], "transient": BARS["rom"],
            "dss_transient": BARS["dss_f32"], "dtpm": BARS["dss_f32"],
            "auto_steady": float(router.tol),
            "family_steady": BARS["family_f32_cg"]}
    for kind, err in errs.items():
        report("oracle", kind=kind, n=len(lat[kind]), max_err_c=err,
               bar_c=bars[kind],
               p50_latency_s_setup_info=float(np.median(lat[kind])))
        check(err <= bars[kind], f"oracle {kind} error {err:.3e} C over "
                                 f"its bar {bars[kind]:g}")
    open_rungs = [r for r, s in breakers.items() if s["state"] != "closed"]
    report("oracle", kind="summary", requests=len(work),
           breakers_open=open_rungs, warm_s_setup_info=warm_s)
    check(not open_rungs, f"router breakers not closed: {breakers}")


def sweep_inputs(rng, b: int, system: str = "2p5d_64"):
    from repro.core import PackageFamily, package_from_name
    from repro.core.workloads import P2P5D
    pkg, n_src = package_from_name(system)
    family = PackageFamily(pkg, params=SWEEP_PARAMS)
    seed = int(rng.integers(1 << 16))
    # drawn in slices: sample_params validates with (B, E) temporaries
    params = np.vstack([family.sample_params(min(1000, b - s), seed=seed + s)
                        for s in range(0, b, 1000)])
    q = np.broadcast_to(wl1_row(n_src, rng, P2P5D), (b, n_src))
    return family, params, np.ascontiguousarray(q)


def run_sweep(family, params, q, chunk: int, mesh=None):
    from repro.core import build_family
    sim = build_family(family, "rc", solver="cg", chunk_size=chunk,
                       mesh=mesh)
    t0 = time.perf_counter()
    temps = np.asarray(sim.observe_batch(sim.steady_state_batch(params, q),
                                         params))
    return sim, temps, time.perf_counter() - t0


def phase_sweep(rng, b: int = 10_000, chunk: int = 2048,
                n_check: int = 64) -> None:
    family, params, q = sweep_inputs(rng, b)
    sim, temps, wall = run_sweep(family, params, q, chunk)
    stats = sim.last_cg_stats
    conv = np.asarray(stats.converged)
    its = np.asarray(stats.iterations)
    picks = rng.choice(b, size=min(n_check, b), replace=False)
    err = max(max_err(temps[i], host_steady_obs(
        host_network(family.instantiate(params[i])), q[i], sim.tags))
        for i in picks)
    report("sweep", candidates=b, chunk=chunk, checked=len(picks),
           max_err_c=err, bar_c=BARS["family_f32_cg"],
           cg_iterations_mean=float(its.mean()),
           cg_iterations_max=int(its.max()),
           converged_share=float(conv.mean()),
           wall_s_setup_info=wall)
    check(temps.shape == (b, len(sim.tags)) and np.isfinite(temps).all(),
          f"sweep output {temps.shape} not finite/expected")
    check(conv.all(), f"sweep: {int((~conv).sum())} candidates unconverged")
    check(err <= BARS["family_f32_cg"],
          f"sweep error {err:.3e} C over its bar")


def phase_sharded(rng, b: int = 10_000, chunk: int = 2048,
                  k: int = 4) -> None:
    family, params, q = sweep_inputs(rng, b)
    one, t_one, wall_one = run_sweep(family, params, q, chunk)
    mesh, t_k, wall_k = run_sweep(family, params, q, chunk, mesh=k)
    err = max_err(t_k, t_one)
    # one chunk through the mesh executor stays on the device: its rows
    # must sit on k distinct devices, chunk / k rows each
    th = mesh.steady_state_batch(params[:chunk], q[:chunk])
    shards = th.addressable_shards
    devices = {s.device for s in shards}
    rows = sorted(s.data.shape[0] for s in shards)
    conv = np.asarray(mesh.last_cg_stats.converged)
    report("sharded", candidates=b, chunk=chunk, mesh=k,
           max_err_vs_one_chip_c=err, bar_c=BARS["family_f32_cg"],
           shard_devices=len(devices), shard_rows=rows,
           converged_share=float(conv.mean()),
           wall_s_one_chip_setup_info=wall_one,
           wall_s_mesh_setup_info=wall_k)
    check(err <= BARS["family_f32_cg"],
          f"sharded sweep differs from one chip by {err:.3e} C")
    check(len(devices) == k and rows == [chunk // k] * k,
          f"chunk shards on {len(devices)} devices with rows {rows}")
    check(np.asarray(one.last_cg_stats.converged).all() and conv.all(),
          "sharded sweep: unconverged candidates")


def check_counters() -> None:
    from repro.kernels.fused_cg.ops import fallback_counts, \
        unconverged_counts
    fb, unc = fallback_counts(), unconverged_counts()
    report("counters", fallbacks=fb, unconverged=unc)
    check(not fb, f"numerical fallbacks fired: {fb}")
    check(not unc, f"unconverged CG solves: {unc}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-sharded sweep and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)
    device = phase_preflight()
    check(device["count"] >= args.chips,
          f"--chips {args.chips} but {device['count']} device(s) present")
    import jax
    from repro.runtime import use_compile_cache
    import repro.core  # noqa: F401 — sets the f32 matmul precision
    report("setup", compile_cache=use_compile_cache(), seed=args.seed,
           matmul_precision=jax.config.jax_default_matmul_precision)
    rng = np.random.default_rng(args.seed)
    if args.chips == 4:
        phase_sharded(rng)
    else:
        phase_steady(rng)
        phase_oracle(rng)
        phase_sweep(rng)
    check_counters()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
