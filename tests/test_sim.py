"""Cycle-level simulator reproduces the paper's qualitative claims."""
import numpy as np
import pytest

from repro.core import FragmentationPolicy, SLOPolicy
from repro.sim.scenarios import (make_tenants, run_congestor_victim_compute,
                                 run_hol_blocking, run_standalone,
                                 service_time_vs_ppb)
from repro.sim.workloads import WORKLOADS, ppb, spin_workload
from repro.sim.traffic import equal_share_traces
from repro.sim.engine import Simulator
from repro.configs.osmosis_pspin import PSPIN


def test_clock_ghz_scales_cycle_costs():
    """Regression for the cycles-vs-ns unit bug the static checker found:
    hardware costs expressed in PU cycles (DMA setup, kernel compute,
    fragmentation overhead) must pass through ``PsPINConfig.cycles_ns``
    before touching the ns event clock.  Before the fix raw cycle counts
    were added onto the clock, which was only correct at the default
    1 GHz; a 2 GHz part must finish a compute-only kernel in exactly
    half the virtual time."""
    from repro.configs.osmosis_pspin import PsPINConfig
    from repro.sim.fastpath import BatchedSimulator
    from repro.sim.traffic import TracePacket

    wl = spin_workload("spin", 2.0)            # pure compute, no IO
    payload = 512 - PSPIN.header_bytes
    cycles = PSPIN.dma_setup_cycles + wl.compute_cycles(payload)
    for cls in (Simulator, BatchedSimulator):
        done = {}
        for ghz in (1.0, 2.0):
            sim = cls(make_tenants([wl]), hw=PsPINConfig(clock_ghz=ghz),
                      record_completions=True)
            res = sim.run([TracePacket(0.0, 0, 512)])
            (tenant, t_done), = res.completions
            assert tenant == 0
            done[ghz] = t_done
        assert done[1.0] == pytest.approx(cycles)       # 1 cycle == 1 ns
        assert done[2.0] == pytest.approx(cycles / 2.0)


def test_cycles_ns_exact_at_default_clock():
    """At 1 GHz the conversion is an exact ``* 1.0`` so historical
    golden traces stay bit-identical."""
    from repro.configs.osmosis_pspin import PsPINConfig
    assert PSPIN.cycles_ns(13) == 13.0
    assert PsPINConfig(clock_ghz=2.0).cycles_ns(13) == 6.5


def test_fig9_wlbvt_fairer_than_rr():
    rr = run_congestor_victim_compute("rr", duration_us=80)
    wl = run_congestor_victim_compute("wlbvt", duration_us=80)
    # RR lets the 2x-costlier congestor take ~2x the PUs (Jain ~0.9);
    # WLBVT restores ~equal occupancy (Jain ~1.0).
    assert wl.jain_pu_timeavg > 0.98
    assert rr.jain_pu_timeavg < wl.jain_pu_timeavg - 0.05


def test_fig9_priority_proportional_shares():
    """2x priority => ~2x PU occupancy under contention (R6 SLO knob)."""
    # cpb sized so each tenant alone demands ~18 of 32 PUs => contention
    wl = spin_workload("spin", 6.0)
    tenants = make_tenants([wl, wl], priorities=[2.0, 1.0])
    trace = equal_share_traces(2, sizes=[512, 512], duration_ns=80_000,
                               seed=0)
    sim = Simulator(tenants, scheduler="wlbvt", record_timeline=True)
    res = sim.run(trace)
    occ = res.timeline["occup"]
    qlen = res.timeline["qlen"]
    # only windows where BOTH tenants are backlogged reflect the contention
    # split (once one drains, work conservation hands over its PUs)
    both = (qlen > 0).all(axis=1)
    sat = occ[both]
    assert len(sat) > 5
    means = sat.mean(axis=0)
    assert means[0] / means[1] == pytest.approx(2.0, rel=0.35)


def test_fig10_fragmentation_resolves_hol_blocking():
    off = run_hol_blocking(FragmentationPolicy(mode="off"), arb="fifo",
                           duration_us=60)
    hw = run_hol_blocking(
        FragmentationPolicy(mode="hardware", fragment_bytes=512),
        duration_us=60)
    # victim (64B transfers) p99 improves by >= 5x (paper: order of magnitude)
    assert off.p99(1) / max(hw.p99(1), 1e-9) > 5.0
    # congestor throughput cost bounded (paper: ~2x worst case)
    assert hw.throughput_gbps(0) > 0.3 * off.throughput_gbps(0)


def test_fig10_software_fragmentation_costs_congestor_throughput():
    hw = run_hol_blocking(
        FragmentationPolicy(mode="hardware", fragment_bytes=512),
        duration_us=60)
    sw = run_hol_blocking(
        FragmentationPolicy(mode="software", fragment_bytes=512),
        duration_us=60)
    # software fragmentation pays per-fragment PU overhead -> <= hw tput
    assert sw.throughput_gbps(0) <= hw.throughput_gbps(0) + 1e-9
    # but still fixes the victim's HoL-blocking
    off = run_hol_blocking(FragmentationPolicy(mode="off"), arb="fifo",
                           duration_us=60)
    assert off.p99(1) / max(sw.p99(1), 1e-9) > 3.0


def test_fig11_osmosis_overhead_bounded_compute():
    """Standalone compute-bound workloads: OSMOSIS within ~3% of baseline."""
    for name in ("aggregate", "reduce"):
        base = run_standalone(name, pkt_size=1024, osmosis=False,
                              duration_us=50)
        osm = run_standalone(name, pkt_size=1024, osmosis=True,
                             duration_us=50)
        t_b = base.stats[0].completed
        t_o = osm.stats[0].completed
        assert t_o >= 0.95 * t_b, (name, t_o, t_b)


def test_watchdog_kills_and_raises_eq_event():
    from repro.core.events import EventKind
    from repro.sim.traffic import make_trace
    wl = spin_workload("hog", cycles_per_byte=50.0)
    tenants = make_tenants([wl], cycle_limits=[100])
    sim = Simulator(tenants)
    res = sim.run(make_trace(0, size=1024, share=0.05, duration_ns=20_000))
    assert res.stats[0].killed > 0
    kinds = {e.kind for e in res.events}
    assert EventKind.CYCLE_BUDGET_EXCEEDED in kinds


def test_fifo_queue_overflow_emits_event():
    from repro.core.events import EventKind
    from repro.sim.traffic import make_trace
    wl = spin_workload("hog", cycles_per_byte=1000.0)
    tenants = make_tenants([wl])
    sim = Simulator(tenants, fifo_capacity=4)
    res = sim.run(make_trace(0, size=64, duration_ns=50_000))
    assert res.stats[0].drops > 0
    assert EventKind.QUEUE_OVERFLOW in {e.kind for e in res.events}


def test_fig3_ppb_classification():
    """Compute-bound kernels exceed PPB at small packets; IO-bound >=256B
    fit (paper Fig. 3)."""
    rows = service_time_vs_ppb([64, 1024])
    by = {(w, p): (svc, budget)
          for w, lst in rows.items() for (p, svc, budget) in lst}
    for w in ("aggregate", "reduce", "histogram", "io_read", "io_write"):
        svc, budget = by[(w, 64)]
        assert svc > budget, w                      # <=64B always congests
    svc, budget = by[("io_read", 1024)]
    assert svc <= budget                            # IO-bound fits PPB
    svc, budget = by[("reduce", 1024)]
    assert svc > budget                             # compute-bound never


def test_control_path_priority():
    """EQ/control traffic bypasses a congested AXI queue (R5)."""
    from repro.sim.traffic import make_trace
    wl = WORKLOADS["io_write"]
    tenants = make_tenants([wl])
    sim = Simulator(tenants,
                    frag=FragmentationPolicy(mode="hardware",
                                             fragment_bytes=512))
    # saturate the AXI with large writes
    trace = make_trace(0, size=4096, share=0.9, duration_ns=30_000)
    done_at = {}
    def cb(t):
        done_at["ctrl"] = t
    sim.run(trace, horizon=5_000.0)
    sim.submit_control(64, cb)
    sim.run([], horizon=None)
    assert "ctrl" in done_at


# ---------------------------------------------------------------------------
# TenantStats: fct semantics + bounded kernel-time reservoir (DESIGN.md §8)
# ---------------------------------------------------------------------------
def test_fct_zero_without_arrivals():
    """Completions with no recorded arrival (packets injected before
    registration) must report fct == 0.0 explicitly — not a silently
    collapsed min() against last_completion."""
    from repro.sim.engine import TenantStats
    st = TenantStats()
    assert st.fct == 0.0                       # nothing happened
    st.last_completion = 500.0                 # completion, no arrival
    assert st.first_arrival == float("inf")
    assert st.fct == 0.0
    st.first_arrival = 120.0                   # normal case
    assert st.fct == pytest.approx(380.0)
    st.first_arrival = 600.0                   # degenerate: never negative
    assert st.fct == 0.0


def test_kernel_time_reservoir_bounded_and_exact_below_cap():
    from repro.sim.engine import KT_RESERVOIR_CAP, TenantStats
    st = TenantStats()
    rng = np.random.default_rng(7)
    vals = rng.uniform(10.0, 1000.0, size=KT_RESERVOIR_CAP + 500)
    for v in vals[:100]:
        st.record_kernel_time(float(v))
    # below the cap the sample is complete: exact percentiles
    assert len(st.kernel_times) == 100
    assert st.kernel_time_percentile(50) == pytest.approx(
        float(np.percentile(vals[:100], 50)))
    for v in vals[100:]:
        st.record_kernel_time(float(v))
    # past the cap: bounded memory, exact count/sum, sane percentiles
    assert len(st.kernel_times) == KT_RESERVOIR_CAP
    assert st.kernel_time_count == len(vals)
    assert st.kernel_time_sum == pytest.approx(sum(float(v) for v in vals))
    assert vals.min() <= st.kernel_time_percentile(99) <= vals.max()
    # deterministic: an identical sequence yields an identical reservoir
    st2 = TenantStats()
    for v in vals:
        st2.record_kernel_time(float(v))
    assert np.array_equal(st.kernel_times, st2.kernel_times)


@pytest.mark.parametrize("extra", [-1, 0, 1, "3cap+517"])
def test_kernel_time_bulk_replay_matches_sequential(extra):
    """``record_kernel_times`` (one array draw past the cap) leaves the
    reservoir, count, sum, percentiles and rng state exactly as the
    per-value ``record_kernel_time`` calls do, and the stream continues
    identically afterwards."""
    from repro.sim.engine import KT_RESERVOIR_CAP, TenantStats
    n = (3 * KT_RESERVOIR_CAP + 517 if extra == "3cap+517"
         else KT_RESERVOIR_CAP + extra)
    vals = np.random.default_rng(11).uniform(10.0, 1000.0, size=n)
    bulk, seq = TenantStats(), TenantStats()
    bulk.record_kernel_times(vals)
    for v in vals:
        seq.record_kernel_time(float(v))
    assert np.array_equal(bulk.kernel_times, seq.kernel_times)
    assert bulk.kernel_time_count == seq.kernel_time_count == n
    assert bulk.kernel_time_sum == seq.kernel_time_sum
    for q in (50, 99):
        assert (bulk.kernel_time_percentile(q)
                == seq.kernel_time_percentile(q))
    if n > KT_RESERVOIR_CAP:
        assert (bulk._kt_rng.bit_generator.state
                == seq._kt_rng.bit_generator.state)
    else:
        assert bulk._kt_rng is None and seq._kt_rng is None
    for st in (bulk, seq):
        st.record_kernel_time(12345.0)
    assert np.array_equal(bulk.kernel_times, seq.kernel_times)


def test_sim_kernel_times_bounded_end_to_end():
    """A long congested run keeps per-tenant kernel-time memory at the
    reservoir cap while p50/p99 stay exact running-count-aware."""
    from repro.sim.engine import KT_RESERVOIR_CAP
    wl = spin_workload("spin", 0.2)
    tenants = make_tenants([wl, wl])
    trace = equal_share_traces(2, sizes=[64, 64], duration_ns=400_000,
                               seed=3)
    res = Simulator(tenants).run(trace)
    total = sum(res.stats[i].kernel_time_count for i in range(2))
    assert total == sum(res.stats[i].completed + res.stats[i].killed
                       for i in range(2))
    for i in range(2):
        assert len(res.stats[i].kernel_times) <= KT_RESERVOIR_CAP
        if res.stats[i].kernel_time_count:
            assert res.p99(i) >= res.p50(i) > 0.0
