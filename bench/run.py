#!/usr/bin/env python3
"""Run one benchmark cell once, in this process, on the chips of this
machine, and print its result as the last line of standard output.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names its configuration and
traffic mix; the surface module the configuration names sets it up,
warms up every shape, measures for ``--seconds`` and then compares what
the timed path produced with the plain reference under
``bench/reference/``.  ``--trace 1`` profiles the window and reports the
per-layer metrics instead of the end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.  JAX's compilation cache is kept in
``$JAX_COMPILATION_CACHE_DIR`` or else in ``<checkout>/.jax_cache``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import runner  # noqa: E402
from bench.harness.cell import CellError  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    try:
        out = runner.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=T_START, root=ROOT)
    except runner.NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    except CellError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    runner.emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
