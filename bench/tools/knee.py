#!/usr/bin/env python3
"""Find the knee of an oracle cell: the highest offered rate at which
completions keep up with arrivals over the window, or the number of
closed-loop clients past which the answers per second stop rising.

    python3 bench/tools/knee.py --workload dtpm_fleet.2p5d_64 \\
        --rates 40,60,80,100,120 --seconds 10 --seed 5
    python3 bench/tools/knee.py --workload dtpm_fleet.2p5d_64 \\
        --clients 4,8,16,32,64 --seconds 10 --seed 5

One set-up, then one window per load (same seed, same mix). For each
load it prints the requests sent, those answered by the window's close,
the backlog (sent but unanswered) at each quarter of the window, and the
p50 and p95 latency from the time each request was due.
"""
import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness as H  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    load = ap.add_mutually_exclusive_group(required=True)
    load.add_argument("--rates")
    load.add_argument("--clients")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    c = H.cell(H.spec(), args.workload)
    H.use_compile_cache()
    device = H.devices(int(c["workload"]["chips"]))
    H.use_program()
    loads = ([{"rate": float(r)} for r in args.rates.split(",")]
             if args.rates else
             [{"clients": int(k)} for k in args.clients.split(",")])
    drv = H.driver(c["traffic"]["kind"])
    run = drv.Run(
        c["config"], c["traffic"], H.seed_root(args.seed),
        int(c["workload"]["chips"]), args.seconds)
    for load in loads:
        run.plan(args.seconds, **load)
        e2e = run.measure(args.seconds)
        sent = np.array([p.enq_t for p in run.pending]) - run.t0
        done = np.array([p.enq_t + r.latency_s - run.t0 if r is not None
                         else np.inf
                         for p, r in zip(run.pending, run.responses)])
        quarters = [round(args.seconds * f, 3) for f in (0.25, 0.5, 0.75, 1)]
        backlog = [int((sent <= t).sum() - (done <= t).sum())
                   for t in quarters]
        lat = run.latency * 1e3
        e2e.update(p50_ms=drv.percentile(lat, 50),
                   p95_ms=drv.percentile(lat, 95))
        print(json.dumps({**load, "sent": len(sent),
                          "answered_by_close": int((done <= run.close
                                                    - run.t0).sum()),
                          "backlog_at": dict(zip(map(str, quarters),
                                                 backlog)),
                          "failed": run.counts()["failed"],
                          **e2e, "device": device}), flush=True)
    run.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
