"""Before/after-refactor regression: the batched ``select_k``/DWRR core
must reproduce the seed scalar-loop scheduler's decisions bit-for-bit.

``tests/data/golden_sched.json`` was generated by running
``tests/data/gen_golden.py`` against the pre-refactor tree: it pins the
full per-request schedule (slot, start/finish step, DWRR chunk steps) of
a fixed-seed 3-tenant serving scenario for both scheduler/arbiter pairs,
and completion/timing digests of fixed-seed simulator scenarios.  Any
divergence here means the unified core changed a scheduling decision.
"""
import importlib.util
import json
import os

import pytest

_DATA = os.path.join(os.path.dirname(__file__), "data")


def _gen():
    spec = importlib.util.spec_from_file_location(
        "gen_golden", os.path.join(_DATA, "gen_golden.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def golden():
    with open(os.path.join(_DATA, "golden_sched.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def gen():
    return _gen()


@pytest.mark.parametrize("combo,scheduler,arbiter", [
    ("wlbvt_dwrr", "wlbvt", "dwrr"),
    ("rr_fifo", "rr", "fifo"),
])
def test_engine_schedule_matches_seed(golden, gen, combo, scheduler,
                                      arbiter):
    got = gen.engine_trace(scheduler, arbiter)
    want = golden["engine"][combo]
    # JSON round-trips tuples to lists — normalize before comparing
    got = json.loads(json.dumps(got))
    assert got["schedule"] == want["schedule"]
    assert got["steps"] == want["steps"]
    assert got["decode_steps"] == want["decode_steps"]
    assert got["prefill_chunks"] == want["prefill_chunks"]
    assert got["jain_timeavg"] == pytest.approx(want["jain_timeavg"],
                                                abs=1e-9)


def test_simulator_decisions_match_seed(golden, gen):
    got = json.loads(json.dumps(gen.sim_trace()))
    want = golden["sim"]
    assert set(got) == set(want)
    for scenario in want:
        for key in want[scenario]:
            assert got[scenario][key] == pytest.approx(
                want[scenario][key], rel=1e-9), (scenario, key)


def _flood32(datapath):
    """Per-tenant completed/killed/drops/last_completion and the EQ event
    count of a 400 us flood of the 400 Gb/s link at T = 32: one victim
    and 31 spin congestors that burn their 50k-cycle watchdog budget."""
    from repro.core import ECTX, SLOPolicy
    from repro.sim.fastpath import build_simulator
    from repro.sim.traffic import equal_share_traces
    from repro.sim.workloads import spin_workload
    T, duration_ns = 32, 400e3
    tenants = []
    for i in range(T):
        wl, limit = ((spin_workload("victim", 0.6), 0) if i == 0 else
                     (spin_workload(f"congestor{i}", 200.0), 50000))
        tenants.append(ECTX(
            tenant_id=i, name=wl.name,
            slo=SLOPolicy(priority=1.0, kernel_cycle_limit=limit),
            kernel=wl))
    sim = build_simulator(tenants, datapath=datapath, fifo_capacity=256)
    trace = equal_share_traces(T, sizes=[512] * T, duration_ns=duration_ns,
                               arrays=True)
    res = sim.run(trace if datapath == "batched" else trace.to_packets(),
                  horizon=duration_ns)
    return {"tenants": [[res.stats[i].completed, res.stats[i].killed,
                         res.stats[i].drops, res.stats[i].last_completion]
                        for i in range(T)],
            "n_events": len(res.events)}


@pytest.mark.parametrize("datapath,flood", [
    pytest.param("event", "golden", id="event"),
    pytest.param("batched", "golden", id="batched"),
    pytest.param("batched", "t32", id="batched-flood32"),
])
def test_sim_datapath_golden(golden, gen, datapath, flood):
    """Both simulator datapaths reproduce the golden per-packet
    completion times and EQ event sequence (incl. the ring's eviction
    behavior at capacity) bit-for-bit on a drop/mark/kill-heavy flood
    (DESIGN.md §8).  At T = 32 no golden is stored: the event loop's own
    run is the reference for the batched path."""
    if flood == "golden":
        got = json.loads(json.dumps(gen.sim_datapath_trace(datapath)))
        assert got == golden["sim_datapath"]
    else:
        assert _flood32(datapath) == _flood32("event")


def _run_report(spec, datapath):
    from repro.api import run_scenario
    return run_scenario(spec.replace(datapath=datapath), "sim").to_dict()


def _strip_path_dependent(d):
    """Jain time-averages fold in a different (mathematically equal)
    order on the batched path — documented bounded drift (DESIGN.md §8);
    everything else in the report must match exactly."""
    jain = (d.pop("jain_pu"), d.pop("jain_io"))
    d["spec"].pop("datapath")
    return jain


from _prop import given, settings, st  # noqa: E402


@settings(max_examples=8, deadline=None)
@given(data=st.data())
def test_datapath_runreport_property(data):
    """Randomized traces produce identical RunReports on both sim
    datapaths (modulo the documented Jain fold drift)."""
    from repro.api.spec import (ArrivalSpec, ScenarioSpec, TenantSpec,
                                WorkloadSpec)
    T = data.draw(st.integers(2, 4), label="tenants")
    seed = data.draw(st.integers(0, 2 ** 16), label="seed")
    scheduler = "wlbvt" if data.draw(st.booleans(), label="wlbvt") else "rr"
    fifo = data.draw(st.integers(16, 128), label="fifo")
    tenants = []
    for i in range(T):
        cpb = data.draw(st.floats(0.3, 12.0), label=f"cpb{i}")
        limit = (data.draw(st.integers(500, 4000), label=f"lim{i}")
                 if data.draw(st.booleans(), label=f"wd{i}") else 0)
        io = data.draw(st.booleans(), label=f"io{i}")
        tenants.append(TenantSpec(
            f"t{i}",
            workload=WorkloadSpec(name=f"w{i}", compute_base=40.0,
                                  compute_per_byte=cpb,
                                  io_kind="egress" if io else "none"),
            kernel_cycle_limit=limit,
            arrival=ArrivalSpec(size=data.draw(st.integers(64, 2048),
                                               label=f"size{i}"),
                                share=1.0 / T, seed_offset=i)))
    # half the examples cut the run at a mid-stream horizon — the
    # fixed-measurement-window mode fleet_sweep uses — so the batch
    # cutter's horizon handling is pinned too
    horizon = 6.0 if data.draw(st.booleans(), label="horizon") else 0.0
    spec = ScenarioSpec(name="prop", tenants=tuple(tenants),
                        scheduler=scheduler, fifo_capacity=fifo,
                        duration_us=12.0, horizon_us=horizon, seed=seed)
    ev = _run_report(spec, "event")
    ba = _run_report(spec, "batched")
    jain_ev = _strip_path_dependent(ev)
    jain_ba = _strip_path_dependent(ba)
    assert ev == ba
    assert jain_ev == pytest.approx(jain_ba, rel=1e-9, abs=1e-12)
