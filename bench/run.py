#!/usr/bin/env python3
"""Chip benchmark of the live FL round, one cell per run.

Usage:
  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs the cell named in BENCHMARK.json on the chips of this machine: makes
the weights and the silos' data from the seed, warms up every program the
window runs, drives whole live rounds back to back for ``--seconds``,
then checks the first round against the plain reference.  Prints the
device, peak HBM, compilations in the window and per-round message bytes
on earlier lines, the compared numbers with their limits as the last
lines of standard error, and one JSON result as the last line of
standard output.  With ``--trace 1`` the metrics are the cell's per-layer
ones, read from a profiler trace of the window's first rounds.

Exits non-zero with no result where JAX finds no TPU, or fewer chips than
the cell asks for, or where the program (``src/repro``) is not beside it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()
    if args.seed < 0:
        parser.error("--seed must be a whole number >= 0")
    if not os.path.isdir(os.path.join(REPO, "src", "repro")):
        sys.exit("bench: the program (src/repro) is not in this checkout")
    sys.path.insert(0, REPO)
    from bench import harness

    out = harness.execute(args.workload, args.seed, args.seconds, bool(args.trace), T_START)
    for line in out["lines"]:
        print(line, flush=True)
    for name, c in out["result"]["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out["result"]), flush=True)
    # Nothing may print after the result: skip the interpreter's exit hooks.
    os._exit(0)


if __name__ == "__main__":
    main()
