#!/usr/bin/env python3
"""Record the small profiler trace that ``bench/tests`` reduce.

    python3 bench/tools/record_trace.py <out_dir>

On the chip: one warm-up and one traced fused-CG family solve of eight
3d_16x3 candidates, inside the annotations the harness writes
(``bench.window`` around it all, ``bench.sweep`` around the solve), then
30 ms with the device idle and no annotation, one small device op, and
50 ms with the device idle under ``bench.wait``. The ``.xplane.pb``
lands under ``<out_dir>``.
"""
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness as H  # noqa: E402


def main(out: str) -> int:
    H.use_compile_cache()
    H.devices(1)
    H.use_program()
    import jax
    import numpy as np
    from repro.core import PackageFamily, build_family, package_from_name
    pkg, n_src = package_from_name("3d_16x3")
    fam = PackageFamily(pkg, params=("grid_offsets", "htc_top"))
    sim = build_family(fam, "rc", solver="cg")
    params = np.repeat(fam.base_params()[None], 8, axis=0)
    q = np.ones((8, n_src))

    def solve():
        return np.asarray(sim.observe_batch(sim.steady_state_batch(params, q),
                                            params))

    def tick():
        jax.numpy.ones(8).block_until_ready()

    solve()
    tick()
    H.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.sweep"):
            solve()
        time.sleep(0.03)
        tick()
        with jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(0.05)
    jax.profiler.stop_trace()
    print(H.ROOT, "iterations",
          np.asarray(sim.last_cg_stats.iterations).tolist())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
