"""Readings from which a cell's limits are set.

    python bench/calibrate.py --workload <cell> --seeds 11 12 ... --seconds 2 [--full 0]

For each seed, in one process (compiled programs are shared): the numbers
the cell compares for the program, and for the reference put in the
program's place in the nearest lower precision (the control) and with the
cell's faults planted.  One JSON line per seed on standard output.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import harness  # noqa: E402
from bench.run import configure_jax, require_tpu  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--full", type=int, choices=(0, 1), default=1,
                    help="0: the program and the control only")
    args = ap.parse_args(argv)
    bench = harness.load_benchmark(ROOT)
    cell = harness.load_cell(ROOT, bench, args.workload)
    device = require_tpu(cell.chips)
    configure_jax(ROOT)
    for seed in args.seeds:
        t0 = time.perf_counter()
        ctx = harness.Context(cell, seed, args.seconds, None, t0)
        out = harness.runner(cell).calibrate(ctx, full=bool(args.full))
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "device": device["kind"],
                          "seconds": time.perf_counter() - t0, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
