"""Device datapath (repro.sim.devicepath) + sweep API tests.

The contract under test (DESIGN.md §13): the jit/scan device datapath is
bit-identical to the host ``BatchedSimulator`` on decisions, the EQ
event stream, and telemetry sums — in ``precision="exact"`` mode there
is no tolerance anywhere except the Jain index (whose device fold sums
in a different association order; documented drift, pinned to 1e-9).
The Pallas WLBVT select kernel must match its dense ``jnp_ref`` oracle
bit-exactly, and both must replay ``core.sched_generic.select_round``.
"""
import dataclasses

import numpy as np
import pytest
from _prop import given, settings, st  # hypothesis or seeded fallback

from repro.api import (ArrivalSpec, ScenarioSpec, SweepAxis, SweepSpec,
                       TenantSpec, WorkloadSpec, apply_knob, get_scenario)
from repro.sim.devicepath import (PARITY_STAT_FIELDS, DevicePathError,
                                  device_eligible, host_oracle,
                                  parity_mismatches, run_device,
                                  run_sweep_specs)


def _assert_parity(spec, h, d):
    assert parity_mismatches(spec, h, d) == []


def _fig9(**kw):
    spec = get_scenario("fig9_congestor_victim",
                        duration_us=kw.pop("duration_us", 30.0),
                        **{k: kw.pop(k) for k in ("scheduler",)
                           if k in kw})
    return dataclasses.replace(spec, record_timeline=False, **kw)


# An 8-tenant heterogeneous mix: cost slope, packet size and priority
# differ per tenant, so no two scheduler lanes look alike.
_MIX8 = tuple(
    TenantSpec(f"t{i}",
               workload=WorkloadSpec(name=f"w{i}", compute_base=40.0,
                                     compute_per_byte=0.3 + 0.05 * (i % 7)),
               arrival=ArrivalSpec(size=256 + 64 * (i % 5), share=1.0 / 8,
                                   seed_offset=i),
               priority=1.0 + (i % 3))
    for i in range(8))


# ---------------------------------------------------------------------------
# golden parity: device == host batched, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("leg,impl,kw", [
    ("wlbvt", "jnp", {}),
    ("wlbvt_ref", "jnp_ref", {}),
    ("wlbvt_pallas", "pallas", {}),
    ("rr", "jnp", {"scheduler": "rr"}),
    ("fifo8", "jnp", {"fifo_capacity": 8}),
    ("horizon", "jnp", {"duration_us": 40.0, "horizon_us": 20.0}),
    ("mix8", "jnp", {"tenants": _MIX8, "duration_us": 20.0}),
])
def test_fig9_parity(leg, impl, kw):
    spec = _fig9(**kw)
    _assert_parity(spec, host_oracle(spec), run_device(spec, impl=impl))


def test_fig9_parity_past_reservoir_cap():
    """Long enough that every tenant's kernel-time reservoir passes its
    cap, so ``_materialize`` runs the bulk Algorithm-R replay; the
    reservoirs and their percentiles must match the host oracle exactly."""
    from repro.sim.engine import KT_RESERVOIR_CAP
    spec = _fig9(duration_us=130.0)
    h, d = host_oracle(spec), run_device(spec, impl="jnp",
                                         precision="exact")
    assert all(s.kernel_time_count > KT_RESERVOIR_CAP
               for s in d.stats.values())
    _assert_parity(spec, h, d)
    for i in range(len(spec.tenants)):
        assert np.array_equal(d.stats[i].kernel_times,
                              h.stats[i].kernel_times)


def test_budget_kill_parity():
    spec = _fig9()
    ten = tuple(dataclasses.replace(t, kernel_cycle_limit=300,
                                    total_cycle_limit=20000)
                for t in spec.tenants)
    spec = dataclasses.replace(spec, tenants=ten)
    h, d = host_oracle(spec), run_device(spec)
    assert sum(s.killed for s in h.stats.values()) > 0  # kills exercised
    _assert_parity(spec, h, d)


def test_sweep_batch_matches_single_replica_runs():
    """vmap correctness: an R=3 batch equals three R=1 launches."""
    base = _fig9(duration_us=15.0)
    specs = [dataclasses.replace(base, seed=s) for s in (0, 1, 2)]
    batch = run_sweep_specs(specs, record_completions=True)
    for spec, br in zip(specs, batch):
        sr = run_device(spec)
        assert br.time == sr.time
        assert br.completions == sr.completions
        for i in range(len(spec.tenants)):
            for f in PARITY_STAT_FIELDS:
                assert (getattr(br.stats[i], f)
                        == getattr(sr.stats[i], f)), (spec.seed, i, f)


def test_sweep_rejects_mixed_scheduler():
    a, b = _fig9(), _fig9(scheduler="rr")
    with pytest.raises(DevicePathError):
        run_sweep_specs([a, b])


# ---------------------------------------------------------------------------
# randomized sweep parity (geometry held constant so the compiled launch
# is reused across examples; knobs vary data, not shapes)
# ---------------------------------------------------------------------------
def _mix(prios, slopes, limits, scheduler, seeds):
    T = len(prios)
    tens = tuple(
        TenantSpec(f"t{i}",
                   workload=WorkloadSpec(name=f"w{i}", compute_base=40.0,
                                         compute_per_byte=slopes[i]),
                   arrival=ArrivalSpec(size=512, share=1.0 / T,
                                       seed_offset=i),
                   priority=prios[i], kernel_cycle_limit=limits[i])
        for i in range(T))
    base = ScenarioSpec(name="prop_mix", tenants=tens, duration_us=4.0,
                        scheduler=scheduler)
    return [dataclasses.replace(base, seed=s) for s in seeds]


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_random_sweep_parity(data):
    T = 3
    prios = [data.draw(st.floats(0.5, 4.0)) for _ in range(T)]
    slopes = [data.draw(st.floats(0.0, 0.8)) for _ in range(T)]
    limits = [data.draw(st.integers(0, 1)) * data.draw(
        st.integers(200, 2000)) for _ in range(T)]
    sched = "wlbvt" if data.draw(st.booleans()) else "rr"
    specs = _mix(prios, slopes, limits, sched, seeds=(0, 1))
    device = run_sweep_specs(specs, record_completions=True)
    for spec, d in zip(specs, device):
        h = host_oracle(spec)
        assert d.time == h.time
        assert d.completions == h.completions
        assert ([(e.tenant, e.kind, e.time) for e in d.events]
                == [(e.tenant, e.kind, e.time) for e in h.events])
        for i in range(T):
            for f in PARITY_STAT_FIELDS:
                assert (getattr(d.stats[i], f)
                        == getattr(h.stats[i], f)), (spec.seed, i, f)


# ---------------------------------------------------------------------------
# WLBVT select kernel: jnp == jnp_ref == pallas == scalar oracle
# ---------------------------------------------------------------------------
def _rand_round(rng, R, T, num_pus):
    prio = rng.uniform(0.5, 4.0, (R, T)).astype(np.float32)
    ql = rng.randint(0, 6, (R, T)).astype(np.int32)
    co = rng.randint(0, 3, (R, T)).astype(np.int32)
    to = (rng.uniform(0.0, 5e4, (R, T))).astype(np.float32)
    bvt = (rng.uniform(0.0, 2e4, (R, T))).astype(np.float32)
    free = rng.randint(0, num_pus + 1, (R,)).astype(np.int32)
    return prio, ql, co, to, bvt, free


@pytest.mark.parametrize("max_picks", [1, 4, 16])
def test_select_rounds_impls_bit_exact(max_picks):
    from repro.kernels.wlbvt_select import wlbvt_select_rounds
    rng = np.random.RandomState(7)
    args = _rand_round(rng, R=11, T=5, num_pus=32)
    outs = {}
    for impl in ("jnp", "jnp_ref", "pallas"):
        picks, ql, co = wlbvt_select_rounds(
            *args, num_pus=32, max_picks=max_picks, impl=impl,
            interpret=True)
        outs[impl] = (np.asarray(picks), np.asarray(ql), np.asarray(co))
    for impl in ("jnp", "pallas"):
        for a, b in zip(outs[impl], outs["jnp_ref"]):
            np.testing.assert_array_equal(a, b, err_msg=impl)


def test_select_rounds_matches_scalar_oracle():
    """Row-by-row replay of core.sched_generic.select_round — the same
    sequential kernel the host scheduler steps through."""
    from repro.core import sched_generic as G
    from repro.kernels.wlbvt_select import wlbvt_select_rounds
    rng = np.random.RandomState(3)
    num_pus, max_picks = 16, 8
    prio, ql, co, to, bvt, free = _rand_round(rng, R=9, T=4, num_pus=num_pus)
    picks, qlo, coo = wlbvt_select_rounds(
        prio, ql, co, to, bvt, free, num_pus=num_pus, max_picks=max_picks,
        impl="jnp_ref")
    picks = np.asarray(picks)
    for r in range(prio.shape[0]):
        q, c = ql[r].copy(), co[r].copy()
        for k in range(max_picks):
            if k < free[r]:
                idx, q, c = G.select_round(prio[r], q, c, to[r], bvt[r],
                                           num_pus, np)
            else:
                idx = -1
            assert picks[r, k] == idx, (r, k)
        np.testing.assert_array_equal(np.asarray(qlo)[r], q)
        np.testing.assert_array_equal(np.asarray(coo)[r], c)


def test_select_rounds_rejects_oversize():
    from repro.kernels.wlbvt_select import wlbvt_select_rounds
    rng = np.random.RandomState(0)
    args = _rand_round(rng, R=2, T=200, num_pus=8)
    with pytest.raises(ValueError):
        wlbvt_select_rounds(*args, num_pus=8, max_picks=1, impl="pallas",
                            interpret=True)


@pytest.mark.parametrize("backend,dtype,pallas", [
    ("tpu", np.float32, True),
    ("tpu", np.float64, False),     # the TPU kernel compiler has no f64
    ("cpu", np.float32, False),
])
def test_select_auto_impl(monkeypatch, backend, dtype, pallas):
    """Auto impl takes the Pallas kernel only for f32 lanes on a TPU."""
    import functools

    import jax
    from repro.kernels.wlbvt_select import wlbvt_select_rounds
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    prio, ql, co, to, bvt, free = _rand_round(np.random.RandomState(0),
                                              R=8, T=4, num_pus=8)
    fn = functools.partial(wlbvt_select_rounds, num_pus=8, max_picks=1)
    with jax.enable_x64(dtype == np.float64):
        f = [a.astype(dtype) for a in (prio, to, bvt)]
        jaxpr = jax.make_jaxpr(fn)(f[0], ql, co, f[1], f[2], free)
    assert ("pallas_call" in str(jaxpr)) == pallas


# ---------------------------------------------------------------------------
# sweep spec API
# ---------------------------------------------------------------------------
def _base2():
    return _fig9(duration_us=10.0)


def test_apply_knob_paths():
    spec = _base2()
    assert apply_knob(spec, "fifo_capacity", 64).fifo_capacity == 64
    s = apply_knob(spec, "tenants.1.priority", 9.0)
    assert s.tenants[1].priority == 9.0 and s.tenants[0].priority \
        == spec.tenants[0].priority
    s = apply_knob(spec, "tenants.*.kernel_cycle_limit", 123)
    assert all(t.kernel_cycle_limit == 123 for t in s.tenants)
    s = apply_knob(spec, "tenants.0.workload.compute_per_byte", 0.25)
    assert s.tenants[0].workload.compute_per_byte == 0.25
    with pytest.raises(KeyError):
        apply_knob(spec, "no_such_field", 1)


def test_sweep_spec_expansion_and_serde():
    sw = SweepSpec(
        name="s", base=_base2(),
        axes=(SweepAxis("fifo_capacity", (64, 4096)),
              SweepAxis("tenants.0.priority", (1.0, 2.0, 4.0))),
        seeds=(0, 1))
    assert len(sw) == 12
    pairs = list(sw.replicas())
    assert len(pairs) == 12
    # axes outer (first axis slowest), seeds innermost
    assert [k["seed"] for k, _ in pairs[:2]] == [0, 1]
    assert pairs[0][0]["fifo_capacity"] == 64
    assert pairs[-1][0] == {"fifo_capacity": 4096,
                            "tenants.0.priority": 4.0, "seed": 1}
    for knobs, spec in pairs:
        assert spec.fifo_capacity == knobs["fifo_capacity"]
        assert spec.tenants[0].priority == knobs["tenants.0.priority"]
        assert spec.seed == knobs["seed"]
    rt = SweepSpec.from_dict(sw.to_dict())
    assert rt == sw and rt.specs() == sw.specs()


def test_device_eligible_gates():
    spec = _base2()
    assert device_eligible(spec) is None
    assert device_eligible(
        dataclasses.replace(spec, record_timeline=True)) is not None
    assert device_eligible(
        dataclasses.replace(spec, scheduler="drr")) is not None
    io_t = dataclasses.replace(
        spec.tenants[0], workload=WorkloadSpec(name="io",
                                               io_kind="dma_read"))
    assert device_eligible(dataclasses.replace(
        spec, tenants=(io_t,) + spec.tenants[1:])) is not None
    with pytest.raises(DevicePathError):
        run_sweep_specs([dataclasses.replace(spec, record_timeline=True)])


def test_summary_row_shape():
    spec = _base2()
    row = run_device(spec, precision="fast").summary_row({"seed": 3})
    assert row["scenario"] == spec.name and row["knobs"] == {"seed": 3}
    assert len(row["tenants"]) == len(spec.tenants)
    for t in row["tenants"]:
        for k in ("name", "completed", "drops", "killed", "ecn_marks",
                  "throughput_gbps", "p50_kernel_ns", "p99_kernel_ns"):
            assert k in t
