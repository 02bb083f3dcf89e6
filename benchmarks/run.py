"""Benchmark orchestrator — one function per paper table/figure.
Prints ``name,value,derived`` CSV rows (see individual modules for
methodology). Fast mode by default; --full reproduces the paper grid."""
from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro.runtime import use_compile_cache


def bench_dss_kernel():
    """us/call of the DSS step kernel vs its oracle (N=640, paper's
    largest RC network)."""
    import jax.numpy as jnp
    from repro.kernels.dss_step.ops import dss_step
    from repro.kernels.dss_step.ref import dss_step_ref
    rng = np.random.default_rng(0)
    b, n, s = 64, 640, 64
    th = jnp.asarray(rng.normal(size=(b, n)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, s)), jnp.float32)
    adt = jnp.asarray(rng.normal(size=(n, n)) * 0.01, jnp.float32)
    bdt = jnp.asarray(rng.normal(size=(s, n)), jnp.float32)
    for name, fn in [("dss_step_xla", lambda: dss_step(th, q, adt, bdt,
                                                       backend="xla")),
                     ("dss_step_ref", lambda: dss_step_ref(th, q, adt,
                                                           bdt))]:
        jax.block_until_ready(fn())
        t0 = time.perf_counter()
        for _ in range(20):
            jax.block_until_ready(fn())
        us = (time.perf_counter() - t0) / 20 * 1e6
        print(f"{name},{us:.1f},us_per_call_B{b}_N{n}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--skip", default="",
                    help="comma list: abstraction,accuracy,exec,roofline")
    args = ap.parse_args(argv)
    use_compile_cache()
    skip = set(args.skip.split(",")) if args.skip else set()
    extra = ["--full"] if args.full else []

    print("name,value,derived")
    bench_dss_kernel()
    if "abstraction" not in skip:
        from benchmarks import abstraction
        abstraction.main(fast=not args.full)
    if "accuracy" not in skip:
        from benchmarks import accuracy
        accuracy.main(extra)
    if "exec" not in skip:
        from benchmarks import exec_time
        exec_time.main(extra)
    if "roofline" not in skip:
        from benchmarks import roofline
        try:
            roofline.main([])
        except Exception as e:  # dry-run artifacts may not exist yet
            print(f"roofline,SKIPPED,{e!r}")
    print("benchmarks done", file=sys.stderr)


if __name__ == "__main__":
    main()
