#!/usr/bin/env python3
"""Find the knee of a serving cell: run its mix at several offered rates
in one process (weights and programs built once) and print, per rate,
the end-to-end numbers and how the backlog moved through the window.

    python3 bench/tools/knee.py --workload serve.qwen3-8b-l16.chat3 \\
        --seed 11 --seconds 40 --rates 1.5,2,2.5,3,3.5

The knee is the highest rate whose backlog (requests arrived and not yet
finished) does not grow from the window's first half to its second.  The
cell's mix file then takes 0.8 x that rate as a number.  Needs the chip.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench.harness import cell as C  # noqa: E402
from bench.harness import runner  # noqa: E402
from bench.harness import traffic as TF  # noqa: E402


def backlog(cell, t_open: float, t_end: float, points: int = 8):
    """Requests arrived and not finished at evenly spaced instants."""
    out = []
    for k in range(1, points + 1):
        tau = t_open + (t_end - t_open) * k / points
        n = 0
        for r in cell.reqs:
            if r["arrival"] > tau:
                continue
            done = (len(r["times"]) >= r["req"].max_new_tokens
                    and r["times"][-1] <= tau)
            n += not done
        out.append(n)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bm = C.load_benchmark(ROOT)
    w, cfg, traffic = C.load_cell(bm, args.workload, ROOT)
    runner.use_compile_cache(ROOT)
    devs = runner.accelerator(int(w["chips"]))
    mod = C.surface(cfg["surface"], ROOT)
    cell = mod.Cell(cfg, traffic, args.seed, devs[:int(w["chips"])],
                    args.seconds)
    cell.setup()
    print(f"set-up {time.perf_counter() - T_START:.1f} s", flush=True)
    for rate in [float(x) for x in args.rates.split(",")]:
        cell.mix = {**traffic, "rate_per_s": rate}
        cell.schedule = TF.serve_requests(cell.mix, args.seed, args.seconds,
                                          cfg["vocab_size"])
        cell.engine = cell._engine(cell.exe)
        cell.steps, cell.reqs = [], []
        cell.exe.calls.clear()
        e2e = cell.measure(args.seconds)
        t_open, t_end = cell.window
        bl = backlog(cell, t_open, t_end)
        half = len(bl) // 2
        print(json.dumps({
            "rate_per_s": rate, **e2e, "requests": len(cell.reqs),
            "backlog": bl,
            "backlog_growth": float(np.mean(bl[half:]) - np.mean(bl[:half])),
            "steps": len(cell.steps),
            "late_p99_ms": float(np.percentile(cell.lateness, 99) * 1e3)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
