#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload sweep.2p5d_64 --seed 7 --seconds 30 --trace 0

Set-up (imports, inputs from ``--seed``, model builds, one warm call of
every shape the cell's traffic uses) counts as ``setup_s``. The window
then runs for ``--seconds``; with ``--trace 1`` it runs under the JAX
profiler and the cell's per-layer metrics are read from the trace and
the program's counts, otherwise its end-to-end metrics are reported.
After the window the answers are compared with the host float64
reference; every number compared is printed beside its limit on
standard error and under ``checks`` in the result line. The last line
of standard output is the result, as JSON. Without the chips the cell
asks for, the command exits non-zero and prints no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import harness as H  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def finite(v):
    """JSON has no NaN or infinity: a reading that is not finite (an
    unanswered request's latency, an answer that is NaN) prints as
    +-1e300, which fails every limit and every bound."""
    v = float(v)
    return v if math.isfinite(v) else math.copysign(1e300, v)


def read_per_layer(entries, ctx) -> dict:
    out = {}
    for m in entries:
        got = H.reader(m["name"]).read(ctx)
        if got is None:
            continue
        got = got if isinstance(got, dict) else {"value": got}
        out[m["name"]] = {"value": finite(got.pop("value")),
                          "unit": m["unit"], **got}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    spec = H.spec()
    c = H.cell(spec, args.workload)
    chips = int(c["workload"]["chips"])
    H.use_compile_cache()
    device = H.devices(chips)
    H.use_program()
    counter = H.CompileCounter()
    run = H.driver(c["traffic"]["kind"]).Run(
        c["config"], c["traffic"], H.seed_root(args.seed), chips,
        args.seconds)
    # a long-running service has long since moved its start-up objects
    # out of the collector's way: a full collection in the window then
    # walks only what the window made
    gc.collect()
    gc.freeze()
    setup_s = time.monotonic() - T_START
    log(f"setup_s {setup_s:.3f}")

    import jax
    trace_dir = H.ROOT / ".bench_trace" / args.workload
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        H.start_trace(trace_dir)
    counter.active = True
    with jax.profiler.TraceAnnotation("bench.window"):
        e2e = run.measure(args.seconds)
    counter.active = False
    if args.trace:
        t = time.monotonic()
        jax.profiler.stop_trace()
        log(f"trace_stop_s {time.monotonic() - t:.3f}")
    log(f"compiles_in_window lowered={counter.lowered} "
        f"compiled={counter.compiled}")
    device["memory_peak_bytes"] = H.memory_peak_bytes()
    counts = run.counts()
    run.release()

    breakdown = None
    if args.trace:
        from bench import trace as T
        t = time.monotonic()
        summary = T.summarize(T.find_xplane(str(trace_dir)))
        log(f"trace_read_s {time.monotonic() - t:.3f}")
        ctx = {"trace": summary, "counts": counts, "config": c["config"],
               "traffic": c["traffic"], "chips": chips,
               "peaks": H.peaks(device["kind"])}
        metrics = read_per_layer(H.metrics_for(spec, args.workload, True),
                                 ctx)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = {"device_ops": summary.top_ops(10),
                     "idle_gaps": summary.gaps[:10]}
    else:
        e2e["setup_s"] = setup_s
        metrics = {}
        for m in H.metrics_for(spec, args.workload, False):
            metrics[m["name"]] = {"value": finite(e2e[m["name"]]),
                                  "unit": m["unit"]}

    checks = run.checks(c["limits"])
    correct = all(v <= lim for _, v, lim in checks)
    for name, v, lim in checks:
        log(f"check {name} {v!r} limit {lim!r}")
    line = {"correct": correct, "attempted": counts["attempted"],
            "failed": counts["failed"], "metrics": metrics,
            "device": device,
            **({"breakdown": breakdown} if breakdown else {}),
            "checks": {name: {"value": finite(v), "limit": lim}
                       for name, v, lim in checks}}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except H.BenchError as exc:
        print(f"bench: {exc}", file=sys.stderr, flush=True)
        sys.exit(2)
