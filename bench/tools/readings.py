#!/usr/bin/env python3
"""Read the numbers a cell's check compares over many seeds, in one
process: the lower readings (the program as the configuration states it)
and, with ``--control``, the control's (the cell's driver says which:
the program's own bfloat16 path, or the reference stepped in bfloat16 in
the program's place).

    python3 bench/tools/readings.py --workload sweep.2p5d_64 \\
        --seeds 11,12,13 --seconds 1 [--control] [--out FILE]

Each seed runs the cell's own set-up, a short window at the cell's own
sizes and load (``--seconds``; a sweep cell always finishes one whole
sweep), and the cell's check; one JSON line per seed is printed and
appended to ``--out``.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness as H  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    c = H.cell(H.spec(), args.workload)
    chips = int(c["workload"]["chips"])
    H.use_compile_cache()
    device = H.devices(chips)
    H.use_program()
    drv = H.driver(c["traffic"]["kind"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        run = drv.Run(c["config"], c["traffic"], H.seed_root(seed), chips,
                      args.seconds, control=args.control)
        run.measure(args.seconds)
        counts = run.counts()
        run.release()
        checks = run.checks(c["limits"])
        line = {"workload": args.workload, "seed": seed,
                "control": args.control, "device": device,
                "attempted": counts["attempted"], "failed": counts["failed"],
                "seconds": time.monotonic() - t0,
                "checks": {n: v for n, v, _ in checks}}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
