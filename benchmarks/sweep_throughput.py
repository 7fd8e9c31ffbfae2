"""Device-sweep throughput: scenarios/second of the accelerator-resident
replica sweep vs the host batched datapath (DESIGN.md §13.4).

Runs a 256-replica seed sweep of an 8-tenant heterogeneous mix (per-
tenant cost slopes, sizes and priorities all differ, so every scheduler
input lane is exercised) through ``repro.sim.devicepath`` — one jit/scan
launch, replicas vmapped — and times the same replicas one-by-one on the
host ``BatchedSimulator``.  A parity leg pins device decisions to the
host bit-for-bit (per-tenant completed/killed/drops, EQ event stream,
telemetry sums) before any rate is reported.

    PYTHONPATH=src python -m benchmarks.sweep_throughput [--smoke]

``--smoke`` shrinks the sweep (R=32) and exits nonzero below a relaxed
guard (CI gate).  The full run records the ≥20x headline.  Steady-state
rate is measured on a second launch of the *same* sweep: replica count
and trace geometry are compiled into the launch, so warming with a
different sweep would recompile inside the timed region.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

GUARD_SPEEDUP = 20.0        # full-run headline gate
SMOKE_GUARD = 5.0           # CI smoke gate (small R amortizes worse)
MIX_TENANTS = 8
SWEEP_REPLICAS = 256
SMOKE_REPLICAS = 32
HOST_REPLICAS = 8           # host leg: timed subset, rate extrapolates
SMOKE_HOST_REPLICAS = 4


def _mix_spec(T: int, duration_us: float, seed: int = 0):
    """Heterogeneous T-tenant mix: distinct cost slope, packet size and
    priority per tenant (no two scheduler lanes look alike)."""
    from repro.api import (ArrivalSpec, ScenarioSpec, TenantSpec,
                           WorkloadSpec)
    tens = tuple(
        TenantSpec(
            f"t{i}",
            workload=WorkloadSpec(name=f"w{i}", compute_base=40.0,
                                  compute_per_byte=0.3 + 0.05 * (i % 7)),
            arrival=ArrivalSpec(size=256 + 64 * (i % 5), share=1.0 / T,
                                seed_offset=i),
            priority=1.0 + (i % 3))
        for i in range(T))
    return ScenarioSpec(name=f"sweep_mix_T{T}", tenants=tens,
                        duration_us=duration_us, seed=seed)


def _parity(spec) -> bool:
    """Device == host on decisions, EQ stream and telemetry sums."""
    from repro.sim.devicepath import host_oracle, parity_mismatches, run_device
    return not parity_mismatches(spec, host_oracle(spec),
                                 run_device(spec, record_completions=True))


def _measure(R: int, H: int, duration_us: float):
    """(pkts_per_replica, compile_s, device_s, host_s_per_replica)."""
    from repro.sim.devicepath import host_oracle, run_sweep_specs
    base = _mix_spec(MIX_TENANTS, duration_us)
    specs = [dataclasses.replace(base, seed=s) for s in range(R)]
    # cold launch = trace + compile + run; warming with a smaller sweep
    # would change the compiled (R, S) geometry and recompile below
    t0 = time.perf_counter()
    res = run_sweep_specs(specs)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = run_sweep_specs(specs)
    dev_s = time.perf_counter() - t0
    n_pkts = sum(st.completed for st in res[0].stats.values())
    t0 = time.perf_counter()
    for s in specs[:H]:
        host_oracle(s, record_completions=False)
    host_s = (time.perf_counter() - t0) / H
    return n_pkts, cold_s, dev_s, host_s


def run(*, smoke: bool = False, duration_us: float = 0.0):
    """(rows, headline) in the benchmarks.run harness convention."""
    if not duration_us:
        duration_us = 20.0 if smoke else 24.0
    R = SMOKE_REPLICAS if smoke else SWEEP_REPLICAS
    H = SMOKE_HOST_REPLICAS if smoke else HOST_REPLICAS
    guard = SMOKE_GUARD if smoke else GUARD_SPEEDUP
    parity_ok = _parity(_mix_spec(MIX_TENANTS, duration_us))
    n_pkts, cold_s, dev_s, host_s = _measure(R, H, duration_us)
    dev_rate, host_rate = R / dev_s, 1.0 / host_s
    speedup = dev_rate / host_rate
    rows = [
        ("leg", "replicas", "scenarios_per_s", "pkts_per_s", "wall_s"),
        ("device_cold", R, round(R / cold_s, 1),
         round(n_pkts * R / cold_s), round(cold_s, 3)),
        ("device_steady", R, round(dev_rate, 1),
         round(n_pkts * dev_rate), round(dev_s, 3)),
        ("host_batched", H, round(host_rate, 1),
         round(n_pkts * host_rate), round(host_s * H, 3)),
    ]
    head = {
        "scenarios_per_sec": round(dev_rate, 1),
        "device_pkts_per_sec": round(n_pkts * dev_rate),
        "host_scenarios_per_sec": round(host_rate, 2),
        "speedup": round(speedup, 1),
        "parity_ok": parity_ok,
        "guard_speedup": guard,
        "guard_ok": bool(speedup >= guard and parity_ok),
    }
    return rows, head


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help=f"R={SMOKE_REPLICAS} sweep; nonzero exit below "
                         f"the {SMOKE_GUARD}x guard or on parity loss")
    ap.add_argument("--duration-us", type=float, default=0.0)
    args = ap.parse_args(argv)
    rows, head = run(smoke=args.smoke, duration_us=args.duration_us)
    for r in rows:
        print(",".join(str(x) for x in r))
    print(head)
    if args.smoke and not head["guard_ok"]:
        print(f"FAIL: device sweep {head['speedup']}x < "
              f"{head['guard_speedup']}x guard (or parity diverged)")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
