#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from: for each seed,
one run of the cell (set-up, a window of ``--seconds``, the comparison
with the reference) and, with ``--control``, the precision control on
the same answers.  All seeds run in this one process.

    python3 bench/tools/readings.py --workload sweep.fig9.r256 \\
        --seeds 101,102,103 --seconds 0 --control

Prints one JSON line per seed: the program's numbers and the control's.
The lower reading of a number is the largest the program gives over a
dozen seeds or more; the upper the smallest the control gives.  Needs
the chip.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import cell as C  # noqa: E402
from bench.harness import runner  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    bm = C.load_benchmark(ROOT)
    w, cfg, traffic = C.load_cell(bm, args.workload, ROOT)
    runner.use_compile_cache(ROOT)
    chips = int(w["chips"])
    devs = runner.accelerator(chips)
    mod = C.surface(cfg["surface"], ROOT)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        cell = mod.Cell(cfg, traffic, seed, devs[:chips], args.seconds)
        cell.setup()
        if args.seconds > 0:
            cell.measure(args.seconds)
        cell.release()
        row = {"seed": seed,
               "program": {n: v for n, v, _ in cell.check()}}
        if args.control:
            row["control"] = dict(cell.control())
        row["wall_s"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
