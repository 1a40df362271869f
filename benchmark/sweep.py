#!/usr/bin/env python3
"""Finds the knee of an open-loop cell, once, on the chip:

    python3 -m benchmark.sweep --workload csi50k-steady --rates 80,120,160 --seconds 15

Runs the cell at each rate in a process of its own (a fresh server each,
the compile cache shared) and prints one line per rate.  The rate the
cell then runs at is a number in its traffic file, about four fifths of
the highest rate here whose backlog did not grow (p50 by thirds of the
window flat, nothing unsettled).  Not part of a check.
"""

from __future__ import annotations

import argparse
import subprocess
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--one", type=float, default=None,
                    help="(internal) run this one rate in this process")
    args = ap.parse_args(argv)
    if args.one is not None:
        from benchmark import run
        return run.run_cell(
            args.workload, args.seed, args.seconds, False,
            overrides={"traffic": {"rate_per_s": args.one, "min_jobs": 1000,
                                   "late_p99_limit_ms": 1e9}})
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        p = subprocess.run(
            [sys.executable, "-m", "benchmark.sweep", "--workload",
             args.workload, "--rates", "0", "--seconds", str(args.seconds),
             "--seed", str(args.seed + k), "--one", str(rate)],
            capture_output=True, text=True)
        keep = [ln for ln in p.stdout.splitlines()
                if ln.startswith(("open:", "window:", "check:", "{"))]
        print(f"=== rate {rate:g}/s exit {p.returncode}")
        print("\n".join(keep)[-3000:])
        if p.returncode:
            print(p.stderr[-1500:])
        sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
