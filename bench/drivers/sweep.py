"""Traffic kind "sweep": a closed loop of back-to-back design-space sweeps.

Set-up draws a pool of ``candidates`` parameter vectors uniformly inside
the configuration's ``sweep_box`` and the PRBS phases of a few WL1
traces, both from the traffic file's ``pool_seed``, builds
``build_family(family, "rc", solver, chunk_size)`` and warms it with one
call at the chunk shape. Sweep ``k`` of the window pairs a fresh
permutation of the pool with power vectors drawn from the PRBS rows,
both from ``(seed, k)``, so no two sweeps repeat an answer. The pool is
the same for every seed, so every seed does the same work, in another
order: a candidate's CG iterations depend on its geometry, so pools
drawn per seed would make the work differ from seed to seed. The window
keeps starting sweeps until ``seconds`` have passed and finishes the one
in flight; the rate is every candidate landed on the host over the time
from the window's start to the end of the last sweep.

The check compares, for a sample drawn from the seed with rows from
every (sweep, chunk) and the last candidate, the temperatures
the executor landed with the host float64 reference of that candidate's
own network; it also counts unconverged and non-finite answers of every
candidate and the program's fallback and unconverged counters.

The control (``control=True``, never in the benchmark's own runs) puts
the reference, solved in bfloat16, in the program's place for the same
sampled candidates: ``bench/reference/lowp.py``. (The program's own
``dtype=bfloat16`` family path does not compile on the TPU.)
"""
from __future__ import annotations

import time

import numpy as np

from bench.drivers.power import wl1_prbs_rows
from bench.reference import lowp
from bench.reference import network as rn
from bench.reference import package as rp

#: streams of the seed's generator, one per use
_POOL, _SWEEP, _SAMPLE, _WARM = range(4)


class Run:
    def __init__(self, cfg, traffic, seed, chips, seconds, control=False):
        import jax.numpy as jnp
        from repro.core import PackageFamily, build_family, package_from_name
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.control = control
        self.b = int(traffic["candidates"])
        self.chunk = int(traffic["chunk_size"])
        pkg, n_src = package_from_name(cfg["preset"])
        family = PackageFamily(pkg, params=tuple(cfg["family_params"]))
        if family.param_names != cfg["param_names"]:
            raise ValueError(f"family parameters {family.param_names} are "
                             f"not the configuration's {cfg['param_names']}")
        rng = np.random.default_rng([int(traffic["pool_seed"]), _POOL])
        box = np.asarray(cfg["sweep_box"], np.float64)
        self.pool = box[:, 0] + rng.random((self.b, box.shape[0])) \
            * (box[:, 1] - box[:, 0])
        self.q_rows = np.vstack([
            wl1_prbs_rows(n_src, cfg["ts"], cfg["power"]["p_max"],
                          seed=int(rng.integers(1 << 62)))
            for _ in range(int(traffic["wl1_traces"]))])
        self.sim = build_family(
            family, "rc", solver=cfg["solver"],
            dtype=getattr(jnp, cfg["dtype"]), chunk_size=self.chunk)
        self.tags = list(self.sim.tags)
        params, q = self._inputs(np.random.default_rng([seed, _WARM]))
        self._solve(params[:self.chunk], q[:self.chunk])
        self.samples = []          # (params, q, landed temps) per sampled row
        self.n_sweeps = 0
        self.iterations = 0
        self.unconverged = 0
        self.nonfinite = 0

    def _inputs(self, rng):
        params = self.pool[rng.permutation(self.b)]
        q = self.q_rows[rng.integers(0, self.q_rows.shape[0], self.b)]
        return params, q

    def _solve(self, params, q):
        th = self.sim.steady_state_batch(params, q)
        return np.asarray(self.sim.observe_batch(th, params))

    def sample_rows(self, k: int) -> np.ndarray:
        """Rows of sweep ``k`` the check compares: ``check_per_stratum``
        from each chunk, the padded last one included, and the last."""
        rng = np.random.default_rng([self.seed, _SAMPLE, k])
        rows = [self.b - 1]
        for lo in range(0, self.b, self.chunk):
            hi = min(lo + self.chunk, self.b)
            rows += list(rng.choice(np.arange(lo, hi),
                                    min(int(self.traffic["check_per_stratum"]),
                                        hi - lo), replace=False))
        return np.unique(rows)

    def measure(self, seconds: float) -> dict:
        import jax
        t0 = time.monotonic()
        while True:
            k = self.n_sweeps
            params, q = self._inputs(
                np.random.default_rng([self.seed, _SWEEP, k]))
            with jax.profiler.TraceAnnotation("bench.sweep"):
                temps = self._solve(params, q)
            stats = self.sim.last_cg_stats
            self.iterations += int(np.asarray(stats.iterations,
                                              np.int64).sum())
            self.unconverged += int((~np.asarray(stats.converged)).sum())
            self.nonfinite += int((~np.isfinite(temps)).any(axis=1).sum())
            self.samples += [(params[i], q[i], temps[i].astype(np.float64))
                             for i in self.sample_rows(k)]
            self.n_sweeps += 1
            if time.monotonic() - t0 >= seconds:
                break
        window = time.monotonic() - t0
        return {"sweep_candidates_per_s": self.n_sweeps * self.b / window}

    def counts(self) -> dict:
        from repro.kernels.fused_cg.ops import (fallback_counts,
                                                unconverged_counts)
        n_chunks = -(-self.b // self.chunk)
        return {"attempted": self.n_sweeps * self.b,
                "failed": self.unconverged + self.nonfinite,
                "candidates": self.n_sweeps * self.b,
                "sweeps": self.n_sweeps,
                "cg_iterations": self.iterations,
                "cg_solves": self.n_sweeps * n_chunks,
                "unconverged": self.unconverged,
                "nonfinite": self.nonfinite,
                "fallbacks": sum(fallback_counts().values()),
                "unconverged_solves": sum(unconverged_counts().values())}

    def release(self) -> None:
        import gc
        self.sim = None
        gc.collect()

    def checks(self, limits: dict) -> list:
        """(name, value, limit) of every number compared."""
        pkg = rp.make_package(self.cfg["preset"])
        nets = [rn.build(rp.candidate(pkg, params))
                for params, _, _ in self.samples]
        want = np.stack([rn.steady_obs(net, q)
                         for net, (_, q, _) in zip(nets, self.samples)])
        cols = [self.tags.index(t) for t in nets[0].tags]
        got = np.stack([s[2][cols] for s in self.samples])
        if self.control:
            got = lowp.steady_obs(nets, [s[1] for s in self.samples],
                                  "bfloat16")
        c = self.counts()
        return [("max_err_c", float(np.abs(got - want).max()),
                 limits["max_err_c"]),
                ("unconverged", c["unconverged"], 0),
                ("nonfinite", c["nonfinite"], 0),
                ("fallbacks", c["fallbacks"], 0),
                ("unconverged_solves", c["unconverged_solves"], 0)]
