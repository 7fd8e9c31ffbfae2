"""Telemetry plane + closed-loop QoS controller (DESIGN.md §6, ISSUE 2).

Covers: collector kernels, numpy<->jnp recording parity (kernel level
and engine level), signal derivation, AIMD/hysteresis controller unit
behavior, both engines' event wiring (ECN, lifetime budget,
backpressure), the closed-loop congestor-vs-victim acceptance demo, and
the <3% recording-overhead budget.
"""
import time

import numpy as np
import pytest

from repro.core.events import EventKind
from repro.core.slo import SLOPolicy
from repro.telemetry import (C_IDX, G_IDX, GAUGES, QoSConfig, QoSController,
                             SignalFrame, Telemetry, bucket_index,
                             compute_signals, create_state, hist_add,
                             hist_quantile, record_step, record_window,
                             ring_mean, tenant_report, wlbvt_service_debt)


# ---------------------------------------------------------------------------
# collector kernels
# ---------------------------------------------------------------------------
def test_bucket_index_log_spacing():
    idx = bucket_index(np.array([0.5, 1.0, 2.0, 3.0, 1024.0, 1e12]), 32, np)
    assert idx.tolist() == [0, 0, 1, 1, 10, 31]   # clipped at both ends


def test_hist_quantile_recovers_percentiles():
    rng = np.random.RandomState(0)
    vals = rng.lognormal(5.0, 1.0, size=4000)
    hist = np.zeros((1, 32))
    for v in vals:
        hist = hist_add(hist, np.array([v]), np.array([True]), np)
    assert hist.sum() == len(vals)
    for q in (0.5, 0.99):
        est = hist_quantile(hist, q, np)[0]
        exact = np.percentile(vals, 100 * q)
        # log2 buckets: estimate within one bucket (2x) of the truth
        assert exact / 2 <= est <= exact * 2


def test_ring_mean_ignores_unwritten_slots():
    st = create_state(2, window=4, xp=np)
    st = record_window(st, np.full((len(GAUGES), 2), 3.0), np)
    st = record_window(st, np.full((len(GAUGES), 2), 5.0), np)
    m = ring_mean(st["ring"], int(st["ptr"]), np)
    assert np.allclose(m, 4.0)                    # not diluted by zeros


def test_telemetry_wrapper_stages_and_commits():
    tel = Telemetry(3)
    tel.inc("arrivals", 0)
    tel.inc("arrivals", 0)
    tel.inc("bytes_in", 1, 512)
    tel.lat(0, 12.0)
    tel.lat(0, 100.0)                             # same tenant, two samples
    tel.commit()
    snap = tel.snapshot()
    assert snap["counts"][0, C_IDX["arrivals"]] == 2
    assert snap["counts"][1, C_IDX["bytes_in"]] == 512
    assert snap["hist"][0].sum() == 2
    assert tel.counter("arrivals")[0] == 2


# ---------------------------------------------------------------------------
# numpy <-> jnp parity (acceptance: recording identical on both backends)
# ---------------------------------------------------------------------------
# avoid exact bucket-boundary latencies only in the *random* sweep; the
# deterministic engine test below covers integer (incl. power-of-2) values
_LAT_POOL = [3.0, 5.0, 7.0, 12.0, 50.0, 100.0, 999.0, 12345.0]


def test_record_step_parity_numpy_vs_jit():
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(1)
    T = 8
    st_np = create_state(T, xp=np)
    st_j = create_state(T, xp=jnp)
    step_j = jax.jit(lambda s, c, v, m: record_step(s, c, v, m, jnp))
    win_j = jax.jit(lambda s, g: record_window(s, g, jnp))
    for _ in range(20):
        ci = rng.randint(0, 5, size=st_np["counts"].shape).astype(float)
        vals = np.array([_LAT_POOL[i] for i in
                         rng.randint(0, len(_LAT_POOL), T)])
        mask = rng.rand(T) < 0.6
        g = rng.randint(0, 100, size=(len(GAUGES), T)).astype(float)
        st_np = record_step(st_np, ci, vals, mask, np)
        st_np = record_window(st_np, g, np)
        st_j = step_j(st_j, ci, vals, mask)
        st_j = win_j(st_j, g)
    assert np.array_equal(st_np["counts"], np.asarray(st_j["counts"]))
    assert np.array_equal(st_np["hist"], np.asarray(st_j["hist"]))
    assert np.array_equal(st_np["ring"], np.asarray(st_j["ring"]))
    assert int(st_np["ptr"]) == int(st_j["ptr"])


def test_telemetry_wrapper_parity_numpy_vs_jnp_backend():
    """The numpy in-place fast path and the jitted jnp path must commit
    identical state for the same staged event sequence."""
    tels = [Telemetry(6, backend=b) for b in ("numpy", "jnp")]
    for step in range(12):
        for tel in tels:
            rng2 = np.random.RandomState(100 + step)
            for t in range(6):
                n = rng2.randint(0, 3)
                for _ in range(n):
                    tel.inc("arrivals", t)
                    tel.lat(t, _LAT_POOL[rng2.randint(0, len(_LAT_POOL))])
                tel.inc("tokens", t, float(rng2.randint(0, 64)))
            tel.commit()
            tel.commit_window(np.full((len(GAUGES), 6), float(step)))
    s_np, s_j = tels[0].snapshot(), tels[1].snapshot()
    assert np.array_equal(s_np["counts"], s_j["counts"])
    assert np.array_equal(s_np["hist"], s_j["hist"])
    assert np.array_equal(s_np["ring"], s_j["ring"])


def test_engine_telemetry_parity_under_jit():
    """End-to-end: the serving engine records the same telemetry whether
    commits run eagerly on numpy or under jax.jit (jnp backend)."""
    from repro.serving.engine import Engine, EngineConfig
    from repro.serving.request import Request

    def run(backend):
        ecfg = EngineConfig(max_slots=4, max_len=128, prefill_chunk=32,
                            max_tenants=4, kv_overcommit=2.0,
                            telemetry_backend=backend)
        eng = Engine(ecfg)
        for t in range(2):
            eng.create_ectx(t, SLOPolicy(kv_quota_tokens=128 * 4))
        rng = np.random.RandomState(0)
        for i in range(12):
            t = i % 2
            plen = 40 if t == 0 else 8
            eng.submit(Request(t, rng.randint(1, 90, plen).astype(np.int32),
                               max_new_tokens=16 if t == 0 else 4))
        eng.run_until_idle()
        return eng.tel.snapshot()

    s_np, s_j = run("numpy"), run("jnp")
    assert np.array_equal(s_np["counts"], s_j["counts"])
    assert np.array_equal(s_np["hist"], s_j["hist"])
    # gauges include fp ratios (kv pressure): fp32 vs fp64 tolerance
    assert np.allclose(s_np["ring"], s_j["ring"], rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# signals
# ---------------------------------------------------------------------------
def test_wlbvt_service_debt_sign():
    # tenant 0 got 2x the normalized service of tenant 1
    debt = wlbvt_service_debt(total_occup=[200.0, 100.0], bvt=[100.0, 100.0],
                              prio=[1.0, 1.0])
    assert debt[0] < 0 < debt[1]                  # 1 is owed service
    assert abs(debt.sum()) < 1e-9


def test_compute_signals_baseline_differencing():
    tel = Telemetry(2)
    tel.inc("arrivals", 0, 10)
    tel.inc("ecn_marks", 0, 5)
    tel.lat(0, 1000.0)
    tel.commit()
    base = tel.snapshot()
    tel.inc("arrivals", 0, 10)                    # clean second interval
    tel.lat(0, 3.0)
    tel.commit()
    kw = dict(prio=np.ones(2), total_occup=np.zeros(2), bvt=np.ones(2))
    cum = compute_signals(tel, **kw)
    itv = compute_signals(tel, baseline=base, **kw)
    assert cum.ecn_rate[0] == pytest.approx(0.25)  # 5 / 20 lifetime
    assert itv.ecn_rate[0] == pytest.approx(0.0)   # interval only
    assert itv.p99[0] < cum.p99[0]                 # old slow sample excluded


# ---------------------------------------------------------------------------
# controller
# ---------------------------------------------------------------------------
def _frame(p99, ecn=0.0, kv=0.0, T=2):
    z = np.zeros(T)
    return SignalFrame(p50=np.asarray(p99, float) / 2,
                       p99=np.asarray(p99, float),
                       ecn_rate=z + ecn, drop_rate=z,
                       service_debt=z, kv_pressure=z + kv,
                       occupancy_mean=z + 1, queue_mean=z,
                       jain_weighted=1.0)


def test_controller_aimd_boosts_then_decays():
    c = QoSController(np.ones(2), p99_targets=[0.0, 100.0])
    a1 = c.update(_frame([500.0, 500.0]))          # tenant 1 violating
    assert a1.weights[1] > 1.0
    assert a1.weights[0] == 1.0                    # no SLO => static weight
    boosted = a1.weights[1]
    for _ in range(20):                            # SLO met: decay to base
        a = c.update(_frame([10.0, 10.0]))
    assert a.weights[1] < boosted
    assert a.weights[1] == pytest.approx(1.0, abs=0.01)


def test_controller_weight_clamped():
    cfg = QoSConfig(ai=10.0, w_max_scale=4.0)
    c = QoSController(np.ones(1), p99_targets=[100.0], cfg=cfg)
    for _ in range(10):
        a = c.update(_frame([1e6], T=1))
    assert a.weights[0] == pytest.approx(4.0)


def test_controller_admission_hysteresis():
    cfg = QoSConfig(pause_hi=0.8, resume_lo=0.4)
    c = QoSController(np.ones(1), p99_targets=[0.0], cfg=cfg)
    assert c.update(_frame([0.0], kv=0.9, T=1)).admit[0] == False  # noqa: E712
    # pressure between lo and hi: stays paused (no chattering)
    assert c.update(_frame([0.0], kv=0.6, T=1)).admit[0] == False  # noqa: E712
    assert c.update(_frame([0.0], kv=0.3, T=1)).admit[0] == True   # noqa: E712
    # and from admitted, mid pressure does not pause
    assert c.update(_frame([0.0], kv=0.6, T=1)).admit[0] == True   # noqa: E712


# ---------------------------------------------------------------------------
# event wiring satellites: ECN, lifetime budget, backpressure
# ---------------------------------------------------------------------------
def test_fmq_marks_before_dropping():
    from repro.core import ECTX, FMQ, PacketDescriptor, PushResult
    q = FMQ(index=0, ectx=ECTX(0, "t", SLOPolicy()), capacity=4)
    assert q.ecn_threshold == 3
    res = [q.push(PacketDescriptor(0, 64, float(i))) for i in range(5)]
    assert res == [PushResult.OK, PushResult.OK, PushResult.MARKED,
                   PushResult.MARKED, PushResult.DROPPED]
    assert q.ecn_marks == 2 and q.drops == 1
    assert q.fifo[2].ecn and not q.fifo[0].ecn


def test_sim_surfaces_ecn_events_and_telemetry():
    from repro.sim.engine import Simulator
    from repro.sim.scenarios import make_tenants
    from repro.sim.traffic import make_trace
    from repro.sim.workloads import spin_workload
    wl = spin_workload("hog", cycles_per_byte=200.0)
    sim = Simulator(make_tenants([wl]), fifo_capacity=8)
    res = sim.run(make_trace(0, size=256, share=0.5, duration_ns=30_000))
    kinds = {e.kind for e in res.events}
    assert EventKind.ECN_MARK in kinds
    marks = res.telemetry.counter("ecn_marks")[0]
    assert marks > 0 and marks == sim.fmqs[0].ecn_marks


def test_sim_total_cycle_budget_kills_with_event():
    from repro.sim.engine import Simulator
    from repro.sim.scenarios import make_tenants
    from repro.sim.traffic import make_trace
    from repro.sim.workloads import spin_workload
    wl = spin_workload("spin", cycles_per_byte=1.0, base=0.0)  # 228 cyc/pkt
    tenants = make_tenants([wl])
    tenants[0].slo = SLOPolicy(total_cycle_limit=800)          # ~3.5 kernels
    sim = Simulator(tenants)
    res = sim.run(make_trace(0, size=256, share=0.2, duration_ns=20_000))
    st = res.stats[0]
    assert st.completed == 3                    # 3*228=684; 4th would be 912
    assert st.killed > 0
    kinds = {e.kind for e in res.events}
    assert EventKind.TOTAL_BUDGET_EXCEEDED in kinds
    assert EventKind.CYCLE_BUDGET_EXCEEDED not in kinds   # distinct cause


def test_serving_total_budget_kills_and_rejects():
    from repro.serving.engine import Engine, EngineConfig
    from repro.serving.request import Request, RequestStatus
    eng = Engine(EngineConfig(max_slots=4, max_len=128, prefill_chunk=32,
                              max_tenants=2))
    eng.create_ectx(0, SLOPolicy(kv_quota_tokens=128 * 4,
                                 total_cycle_limit=30))
    r1 = eng.submit(Request(0, np.ones(16, np.int32), max_new_tokens=8))
    eng.run_until_idle()
    assert r1.status == RequestStatus.DONE      # 24 tokens <= 30
    r2 = eng.submit(Request(0, np.ones(16, np.int32), max_new_tokens=8))
    eng.run_until_idle()
    assert r2.status == RequestStatus.KILLED    # crosses 30 mid-prefill
    r3 = eng.submit(Request(0, np.ones(16, np.int32), max_new_tokens=8))
    assert r3.status == RequestStatus.REJECTED  # budget exhausted up front
    kinds = [e.kind for e in eng.poll_events(0)]
    assert kinds.count(EventKind.TOTAL_BUDGET_EXCEEDED) == 2


def test_apply_to_scheduler_scales_distinct_bases():
    """The controller contributes a boost; each scheduler knob keeps its
    own SLO-configured base weights (prio vs dma vs egress)."""
    from repro.telemetry import apply_to_scheduler
    c = QoSController(np.ones(2), p99_targets=[0.0, 100.0])
    act = c.update(_frame([500.0, 500.0]))        # tenant 1 boosted 1.5x
    prio = np.array([2.0, 1.0])
    dma = np.array([4.0, 8.0])
    apply_to_scheduler(act, (prio, np.array([2.0, 1.0])),
                       (dma, np.array([4.0, 8.0])))
    assert prio.tolist() == [2.0, 1.5]            # bases kept, boost scaled
    assert dma.tolist() == [4.0, 12.0]


def test_counters_are_integer_accumulators():
    """fp32 accumulators saturate at 2^24; counters must be integers on
    both backends so long-run counts keep advancing."""
    tel_np, tel_j = Telemetry(1), Telemetry(1, backend="jnp")
    for tel in (tel_np, tel_j):
        assert np.issubdtype(np.asarray(tel.state["counts"]).dtype,
                             np.integer)
        assert np.issubdtype(np.asarray(tel.state["hist"]).dtype,
                             np.integer)
        tel.inc("bytes_in", 0, float(1 << 24))
        tel.commit()
        tel.inc("bytes_in", 0, 1.0)
        tel.commit()
        assert int(tel.counter("bytes_in")[0]) == (1 << 24) + 1


def test_telemetry_reset_tenant_clears_history():
    tel = Telemetry(2)
    tel.inc("arrivals", 0, 5)
    tel.lat(0, 9.0)
    tel.inc("arrivals", 1, 3)
    tel.commit()
    tel.commit_window(np.ones((len(GAUGES), 2)))
    tel.lat(0, 2.0)                               # staged, uncommitted
    tel.reset_tenant(0)
    tel.commit()
    snap = tel.snapshot()
    assert snap["counts"][0].sum() == 0 and snap["hist"][0].sum() == 0
    assert np.all(snap["ring"][:, 0, :] == 0)
    assert snap["counts"][1, C_IDX["arrivals"]] == 3   # others untouched


def test_controller_reset_tenant_forgets_boost_and_pause():
    c = QoSController(np.ones(2), p99_targets=[0.0, 100.0])
    c.update(_frame([500.0, 500.0]))              # boost tenant 1
    c.paused[1] = True
    c.reset_tenant(1)
    assert c.weights[1] == 1.0 and not c.paused[1]


def test_destroy_ectx_resets_controller_row():
    from repro.serving.engine import Engine, EngineConfig
    from repro.telemetry import QoSController as QC
    eng = Engine(EngineConfig(max_slots=4, max_len=128, max_tenants=2,
                              qos_interval=8))
    ctrl = QC(np.ones(2), p99_targets=[0.0, 10.0])
    eng.attach_controller(ctrl)
    eng.create_ectx(0, SLOPolicy(kv_quota_tokens=128))
    ctrl.weights[0] = 4.0
    ctrl.paused[0] = True
    eng.destroy_ectx(0)
    assert ctrl.weights[0] == 1.0 and not ctrl.paused[0]


def test_attach_controller_rejects_inert_config():
    from repro.serving.engine import Engine, EngineConfig
    from repro.telemetry import QoSController as QC
    eng = Engine(EngineConfig(max_slots=4, max_tenants=2))  # qos_interval=0
    with pytest.raises(ValueError):
        eng.attach_controller(QC(np.ones(2)))
    eng2 = Engine(EngineConfig(max_slots=4, max_tenants=2, telemetry=False,
                               qos_interval=8))
    with pytest.raises(ValueError):
        eng2.attach_controller(QC(np.ones(2)))


def test_sim_backpressure_does_not_poison_drop_signal():
    """Gated arrivals count as 'rejected' in telemetry, not 'drops' —
    drop_rate feeds the controller's pressure signal, and polluting it
    would latch a paused tenant paused forever."""
    from repro.sim.engine import Simulator
    from repro.sim.scenarios import make_tenants
    from repro.sim.traffic import make_trace
    from repro.sim.workloads import spin_workload
    sim = Simulator(make_tenants([spin_workload("w", 0.1)]))
    sim._admit[0] = False
    res = sim.run(make_trace(0, size=256, share=0.05, duration_ns=5_000))
    assert res.stats[0].drops > 0                 # surfaced to the user...
    assert res.telemetry.counter("drops")[0] == 0  # ...not to the signal
    assert res.telemetry.counter("rejected")[0] == res.stats[0].drops


def test_serving_backpressure_gate_rejects_with_event():
    from repro.serving.engine import Engine, EngineConfig
    from repro.serving.request import Request, RequestStatus
    eng = Engine(EngineConfig(max_slots=4, max_len=128, max_tenants=2))
    eng.create_ectx(0, SLOPolicy(kv_quota_tokens=128 * 4))
    eng._admit[0] = False                       # as the controller would
    r = eng.submit(Request(0, np.ones(8, np.int32), max_new_tokens=4))
    assert r.status == RequestStatus.REJECTED
    assert EventKind.BACKPRESSURE in {e.kind for e in eng.poll_events(0)}
    assert eng.tel.staged("rejected")[0] == 1


# ---------------------------------------------------------------------------
# closed loop (acceptance): victim p99 improves, weighted Jain holds
# ---------------------------------------------------------------------------
def test_closed_loop_improves_victim_p99_without_fairness_regression():
    from repro.sim.scenarios import run_qos_closed_loop
    static = run_qos_closed_loop(False, duration_us=200.0)
    closed = run_qos_closed_loop(True, duration_us=200.0)
    p99_s = hist_quantile(static.telemetry.snapshot()["hist"], 0.99, np)
    p99_c = hist_quantile(closed.telemetry.snapshot()["hist"], 0.99, np)
    # victim (tenant 1) p99 sojourn latency improves substantially...
    assert p99_c[1] < 0.55 * p99_s[1]
    # ...and time-averaged weighted Jain fairness does not regress
    assert closed.jain_pu_timeavg >= static.jain_pu_timeavg - 0.05
    # same offered load in both runs
    assert (static.stats[1].completed + static.stats[1].drops
            == closed.stats[1].completed + closed.stats[1].drops)


def test_serving_controller_adapts_weights_and_protects_victim():
    import examples.qos_controller_demo as demo
    static = demo.run(False, rounds=80)
    closed = demo.run(True, rounds=80)
    p99_s = hist_quantile(static.tel.snapshot()["hist"], 0.99, np)
    p99_c = hist_quantile(closed.tel.snapshot()["hist"], 0.99, np)
    assert len(closed.controller.history) > 0
    assert max(a.weights[1] for a in closed.controller.history) > 1.0
    assert p99_c[1] < p99_s[1]


# ---------------------------------------------------------------------------
# report + overhead budget
# ---------------------------------------------------------------------------
def test_tenant_report_structure():
    tel = Telemetry(4)
    tel.inc("arrivals", 2, 7)
    tel.lat(2, 40.0)
    tel.commit()
    rep = tenant_report(tel, names={2: "victim"})
    assert list(rep["tenants"]) == [2]            # only active tenants
    row = rep["tenants"][2]
    assert row["arrivals"] == 7 and row["name"] == "victim"
    assert row["p99_latency"] > 0
    import json
    json.dumps(rep)                               # JSON-able


def _overhead_engine():
    """A smoke-model engine with telemetry on (numpy backend) and a
    standing backlog that keeps it busy through the measurement."""
    from repro.configs import smoke_config
    from repro.serving.engine import Engine, EngineConfig, ModelExecutor
    from repro.serving.request import Request
    ecfg = EngineConfig(max_slots=8, max_len=128, prefill_chunk=32,
                        max_tenants=16, kv_overcommit=4.0, telemetry=True)
    eng = Engine(ecfg, executor=ModelExecutor(smoke_config("qwen3-8b"),
                                              ecfg, rng_seed=0))
    rng = np.random.RandomState(0)
    for t in range(4):
        eng.create_ectx(t, SLOPolicy(kv_quota_tokens=128 * 2))
    for i in range(64):
        eng.submit(Request(i % 4, rng.randint(1, 90, 16).astype(np.int32),
                           max_new_tokens=24))
    return eng


def _time_steps(eng, steps: int, warmup: int = 8) -> float:
    """Mean seconds per engine step after warmup."""
    for _ in range(warmup):
        eng.step()
    t0 = time.perf_counter()
    for _ in range(steps):
        eng.step()
    return (time.perf_counter() - t0) / steps


def _stage_typical(tel) -> None:
    """A representative per-step event load: a few arrivals, token
    charges, and two request completions."""
    for t in range(4):
        tel.inc("arrivals", t)
        tel.inc("tokens", t, 8.0)
    tel.lat(0, 12.0)
    tel.lat(1, 30.0)


def _time_commit(eng, iters: int = 300) -> float:
    """Mean seconds per full telemetry commit (stage + flush + window)."""
    for _ in range(8):
        _stage_typical(eng.tel)
        eng._commit_telemetry()
    t0 = time.perf_counter()
    for _ in range(iters):
        _stage_typical(eng.tel)
        eng._commit_telemetry()
    return (time.perf_counter() - t0) / iters


def test_recording_overhead_within_budget():
    """Acceptance: telemetry recording costs <3% of a model-backed
    engine step.  The commit path (``Engine._commit_telemetry``) is timed
    directly against the steady-state step: its cost (tens of us) is far
    below run-to-run step-time noise, so differencing runs with and
    without telemetry would measure the noise."""
    eng = _overhead_engine()
    step_s = _time_steps(eng, steps=24)
    assert 100.0 * _time_commit(eng) / step_s < 3.0


def test_format_console_labels_time_columns():
    from repro.telemetry import format_console
    tel = Telemetry(2)
    tel.inc("arrivals", 0, 3)
    tel.lat(0, 40.0)
    tel.commit()
    rep = tenant_report(tel)
    header = format_console(rep, time_unit="ns").splitlines()[0]
    assert "p50(ns)" in header and "p99(ns)" in header
    header = format_console(rep, time_unit="steps").splitlines()[0]
    assert "p50(steps)" in header
    # the report's own declared unit wins when none is passed
    rep["latency_unit"] = "steps"
    assert "p99(steps)" in format_console(rep).splitlines()[0]
    with pytest.raises(ValueError):
        format_console(rep, time_unit="seconds")


def test_dump_json_refuses_to_clobber(tmp_path):
    from repro.telemetry import dump_json
    path = str(tmp_path / "report.json")
    dump_json({"a": 1}, path)
    with pytest.raises(FileExistsError):
        dump_json({"a": 2}, path)
    dump_json({"a": 2}, path, overwrite=True)
    import json
    with open(path) as fh:
        assert json.load(fh) == {"a": 2}
