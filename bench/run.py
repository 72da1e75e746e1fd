#!/usr/bin/env python3
"""The Stretto engine's benchmark: one run of one cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> [--control 1]

Cells, metrics and bounds are declared in BENCHMARK.json at the root of
the checkout; `bench/sbench/harness.py` says how a run goes. The last
line of standard output is the run's result as one JSON object; with
`--trace 0` its metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read from a profiler trace of the
window. `--control 1` puts the control (the reference in float8) in
the program's place for the check: `correct` is decided on the control's
answers and reads false, and the program's own numbers go under
`info.program`. Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from sbench import harness
    cell = harness.find_cell(args.workload)
    try:
        harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 3
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, control=bool(args.control))
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
