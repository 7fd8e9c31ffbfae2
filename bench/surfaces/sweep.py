"""Replica-sweep surface: many seeds of one NIC scenario as the replica
lanes of one device launch (``repro.sim.devicepath.run_sweep_specs``,
the call ``repro.launch.sweep.run_sweep`` makes per group).

Set-up builds the run's seed block and runs one launch of it, which
compiles (or loads) the launch for that geometry and warms every other
cost.  The window then repeats the same block back to back; a launch
started in the window runs to its end, and the rate divides by the time
to that end.  A traced run profiles the window's first launch.  The results of the last launch are compared, replica by
replica on a seeded sample, with the plain f64 event loop in
``bench/reference/pspin.py``.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from bench.harness import traffic as TF
from bench.harness.sweep_calls import CALL_SPAN
from bench.reference import pspin as REF

COUNT_FIELDS = ("completed", "killed", "drops", "ecn_marks")
# limits of the numbers compared; their readings are in PERF.md
LIMITS = {"fates": 1e-3, "end_time": 1e-4, "kernel_time_sum": 1e-4}


def program_specs(nic: dict, mix: dict, seeds):
    """The program's ``ScenarioSpec`` of each replica, built from the mix
    data (not from the program's scenario registry)."""
    from repro.api import (ArrivalSpec, ScenarioSpec, TenantSpec,
                           WorkloadSpec)
    from repro.configs.osmosis_pspin import PSPIN
    program_nic = {"num_pus": PSPIN.num_pus, "clock_ghz": PSPIN.clock_ghz,
                   "ingress_gbps": PSPIN.ingress_gbps,
                   "dma_setup_cycles": PSPIN.dma_setup_cycles,
                   "header_bytes": PSPIN.header_bytes}
    for k, v in program_nic.items():
        if float(nic[k]) != float(v):
            raise ValueError(f"program NIC {k}={v} differs from the "
                             f"configuration's {nic[k]}")
    sc = TF.sweep_scenario(mix)
    tenants = tuple(
        TenantSpec(t["name"],
                   workload=WorkloadSpec(
                       name=t["name"], compute_base=t["compute_base"],
                       compute_per_byte=t["compute_per_byte"],
                       spin_factor=t["spin_factor"]),
                   arrival=ArrivalSpec(size=int(t["pkt_bytes"]),
                                       share=float(t["share"]),
                                       seed_offset=int(t["seed_offset"])),
                   priority=float(t["priority"]),
                   kernel_cycle_limit=int(t["kernel_cycle_limit"]),
                   total_cycle_limit=int(t["total_cycle_limit"]))
        for t in sc["tenants"])
    base = ScenarioSpec(name="bench_sweep", tenants=tenants,
                        scheduler=sc["scheduler"],
                        duration_us=sc["duration_us"],
                        horizon_us=sc["horizon_us"],
                        fifo_capacity=sc["fifo_capacity"])
    return [base.replace(seed=s) for s in seeds]


def program_row(res) -> dict:
    """A program result (``DeviceRunResult``) in the reference's form."""
    T = len(res.stats)
    st = [res.stats[i] for i in range(T)]
    return {"time": float(res.time),
            "completed": np.array([s.completed for s in st]),
            "killed": np.array([s.killed for s in st]),
            "drops": np.array([s.drops for s in st]),
            "ecn_marks": np.asarray(res.counters["ecn_marks"]).astype(int),
            "kernel_time_sum": np.array([s.kernel_time_sum for s in st])}


def compare(got: dict, ref: dict) -> dict:
    """The three numbers compared for one replica: the share of packets
    whose fate (completed / killed / dropped / ECN-marked) differs, and
    the relative gaps of the end time and of the worst tenant's
    kernel-time sum."""
    diff = sum(int(np.abs(np.asarray(got[k]) - ref[k]).sum())
               for k in COUNT_FIELDS)
    g = np.asarray(got["kernel_time_sum"], np.float64)
    r = np.asarray(ref["kernel_time_sum"], np.float64)
    ksum = float(np.max(np.where(r != 0, np.abs(g - r) / np.abs(np.where(
        r != 0, r, 1.0)), np.where(g != 0, 1.0, 0.0))))
    return {"fates": diff / max(1, int(ref["arrivals"].sum())),
            "end_time": abs(got["time"] - ref["time"]) / max(ref["time"],
                                                             1e-30),
            "kernel_time_sum": ksum}


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 seconds: float):
        self.nic = config
        self.mix = traffic
        self.seed = int(seed)
        self.seeds = TF.replica_seeds(traffic, seed)
        self.scenario = TF.sweep_scenario(traffic)
        self.calls = []
        self.results = None
        self.attempted = self.failed = 0

    def _launch(self):
        from repro.sim.devicepath import run_sweep_specs
        return run_sweep_specs(self.specs, precision=self.nic["precision"])

    def setup(self) -> None:
        self.specs = program_specs(self.nic, self.mix, self.seeds)
        self.results = self._launch()

    def measure(self, seconds: float, traced=contextlib.nullcontext) -> dict:
        """Launches back to back; a traced run profiles the first one (one
        launch of a long scan fills the profiler's buffer), which is
        like every other: set-up ran the same launch once already."""
        import jax
        t_open = time.perf_counter()
        t_close = t_open + seconds
        n = 0
        while time.perf_counter() < t_close:
            t0 = time.perf_counter()
            with (traced() if n == 0 else contextlib.nullcontext()), \
                    jax.profiler.TraceAnnotation(CALL_SPAN):
                self.results = self._launch()
            t1 = time.perf_counter()
            self.calls.append({"start": t0, "end": t1,
                               "scenarios": len(self.results),
                               "packets": packets(self.results)})
            n += len(self.results)
        t_end = time.perf_counter()
        self.attempted = n
        return {"sweep_scen_per_s": n / (t_end - t_open)}

    def record(self) -> dict:
        return {"calls": self.calls}

    def release(self) -> None:
        self.specs = None

    def sample(self):
        k = min(int(self.mix["check_replicas"]), len(self.seeds))
        pick = np.random.default_rng(self.seed).choice(len(self.seeds), k,
                                                       replace=False)
        return sorted(int(x) for x in pick)

    def _worst(self, rows) -> dict:
        """Worst of each compared number over ``(got, ref)`` rows."""
        worst = {name: 0.0 for name in LIMITS}
        for got, ref in rows:
            c = compare(got, ref)
            for name in LIMITS:
                worst[name] = max(worst[name], c[name])
        return worst

    def check(self):
        """The last timed launch's results against the reference, on
        ``check_replicas`` replicas drawn from the seed."""
        worst = self._worst(
            (program_row(self.results[r]),
             REF.simulate(self.nic, self.scenario, self.seeds[r]))
            for r in self.sample())
        return [(name, worst[name], LIMITS[name]) for name in LIMITS]

    def control(self):
        """The precision control on the same replicas: the reference in
        bfloat16, the width below the configuration's float32, in the
        program's place."""
        import ml_dtypes
        worst = self._worst(
            (REF.simulate(self.nic, self.scenario, self.seeds[r],
                          dtype=ml_dtypes.bfloat16),
             REF.simulate(self.nic, self.scenario, self.seeds[r]))
            for r in self.sample())
        return [(name, worst[name]) for name in LIMITS]


def packets(results) -> int:
    """Simulated packets: the arrivals each replica ran."""
    return int(sum(r.counters["arrivals"].sum() for r in results))
