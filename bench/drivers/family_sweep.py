"""Traffic kind "family_sweep": the design-space sweep of ``sweep.py`` at
the configuration's fidelity, on the cell's chips.

The loop, the candidate pool, the WL1 powers, the samples the check
takes and the counts are those of :class:`bench.drivers.sweep.Run`. What
differs is the model: ``build_family(family, cfg["fidelity"] (default
"rc"), **cfg["build"], solver, dtype, chunk_size, mesh)``, where
``mesh`` is the cell's chips when it has more than one, so every chunk
is split over the chips by the family executor. Set-up warms the model
with one whole sweep, so every program the window runs (each chunk's,
the landed sweep's observation, on one chip or on the mesh) is compiled
before it.

The check compares the sampled candidates with the host float64
reference of the fidelity: ``reference/network.py``'s RC network for
"rc", ``reference/voxel.py``'s voxel grid for "fvm" (solved on a pool
of threads, one candidate and one BLAS thread each), with the same
counters at limit 0. The control puts that reference, solved in bfloat16, in the program's place.
A program whose family model of the fidelity keeps no ``last_cg_stats``
cannot show that its solves converged: the run stops at set-up.
"""
from __future__ import annotations

import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
from threadpoolctl import threadpool_limits

from bench import harness as H
from bench.drivers import sweep
from bench.drivers.power import wl1_prbs_rows
from bench.reference import package as rp
from bench.reference import voxel as rv


class Run(sweep.Run):
    def __init__(self, cfg, traffic, seed, chips, seconds, control=False):
        import jax.numpy as jnp
        from repro.core import PackageFamily, build_family, package_from_name
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.control = control
        self.fidelity = cfg.get("fidelity", "rc")
        self.b = int(traffic["candidates"])
        self.chunk = int(traffic["chunk_size"])
        pkg, n_src = package_from_name(cfg["preset"])
        family = PackageFamily(pkg, params=tuple(cfg["family_params"]))
        if family.param_names != cfg["param_names"]:
            raise ValueError(f"family parameters {family.param_names} are "
                             f"not the configuration's {cfg['param_names']}")
        rng = np.random.default_rng([int(traffic["pool_seed"]),
                                     sweep._POOL])
        box = np.asarray(cfg["sweep_box"], np.float64)
        self.pool = box[:, 0] + rng.random((self.b, box.shape[0])) \
            * (box[:, 1] - box[:, 0])
        self.q_rows = np.vstack([
            wl1_prbs_rows(n_src, cfg["ts"], cfg["power"]["p_max"],
                          seed=int(rng.integers(1 << 62)))
            for _ in range(int(traffic["wl1_traces"]))])
        self.sim = build_family(
            family, self.fidelity, **cfg.get("build", {}),
            solver=cfg["solver"], dtype=getattr(jnp, cfg["dtype"]),
            chunk_size=self.chunk, mesh=chips if chips > 1 else None)
        if not hasattr(self.sim, "last_cg_stats"):
            raise H.BenchError(f"the program's {self.fidelity!r} family "
                               f"model reports no CG stats")
        self.tags = list(self.sim.tags)
        self._solve(*self._inputs(np.random.default_rng([seed,
                                                         sweep._WARM])))
        self.samples = []
        self.n_sweeps = 0
        self.iterations = 0
        self.unconverged = 0
        self.nonfinite = 0

    def checks(self, limits: dict) -> list:
        """(name, value, limit) of every number compared."""
        if self.fidelity == "rc":
            return super().checks(limits)
        opts = {k: self.cfg["build"][k]
                for k in ("dx_target", "dz_target", "max_slabs")}
        pkg = rp.make_package(self.cfg["preset"])

        def grid(params):
            return rv.voxelize(rp.candidate(pkg, params), **opts)

        def reference(sample):
            vox = grid(sample[0])
            return vox.tags, rv.steady_obs(vox, sample[1])

        # one BLAS thread a solve: the threads are the parallelism
        t0, n = time.monotonic(), os.cpu_count() or 1
        with threadpool_limits(1), ThreadPoolExecutor(n) as ex:
            tags, want = zip(*ex.map(reference, self.samples))
        print(f"reference_s {time.monotonic() - t0:.3f} for "
              f"{len(self.samples)} candidates on {n} threads",
              file=sys.stderr, flush=True)
        cols = [self.tags.index(t) for t in tags[0]]
        got = np.stack([s[2][cols] for s in self.samples])
        if self.control:
            got = rv.control_obs([grid(s[0]) for s in self.samples],
                                 [s[1] for s in self.samples], "bfloat16")
        c = self.counts()
        return [("max_err_c", float(np.abs(got - np.stack(want)).max()),
                 limits["max_err_c"]),
                ("unconverged", c["unconverged"], 0),
                ("nonfinite", c["nonfinite"], 0),
                ("fallbacks", c["fallbacks"], 0),
                ("unconverged_solves", c["unconverged_solves"], 0)]
