"""Wall-clock spans of the program (``repro.telemetry.wallclock``).

With a profiler recording, a short serving run and a 2-replica sweep
write every ``osmosis.`` span at its layer boundary, nested in the call
that caused it, with the step and request ids as stats; with none
recording the spans change no result.
"""
import contextlib
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.api import get_scenario
from repro.configs import smoke_config
from repro.core.slo import SLOPolicy
from repro.serving import engine as E
from repro.serving.engine import Engine, EngineConfig, ModelExecutor
from repro.serving.request import Request, RequestStatus
from repro.sim import devicepath as DP

SERVE_SPANS = {"osmosis.serve.admit", "osmosis.serve.prefill.pack",
               "osmosis.serve.prefill.dispatch", "osmosis.serve.prefill.sync",
               "osmosis.serve.decode.dispatch", "osmosis.serve.decode.sync",
               "osmosis.serve.tokens", "osmosis.serve.account"}
SWEEP_SPANS = ("osmosis.sweep.build", "osmosis.sweep.stack",
               "osmosis.sweep.launch", "osmosis.sweep.fetch",
               "osmosis.sweep.materialize")
STEP = "test.step"
CALL = "test.call"
ECFG = EngineConfig(max_slots=4, max_len=128, prefill_chunk=16,
                    prefill_slots_per_step=2, max_tenants=4)


@contextlib.contextmanager
def profiled(logdir):
    """Profile the block; yields a list that holds, after the block, the
    host spans ``(name, start_ns, end_ns, stats)`` sorted by start."""
    out = []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"),
                      recursive=True)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("osmosis.", "test.")):
                        out.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats)))
    out.sort(key=lambda s: s[1])


@pytest.fixture(scope="module")
def exe():
    return ModelExecutor(smoke_config("qwen3-8b"), ECFG, rng_seed=0)


def _serve(exe, step_span: bool = False):
    """Two tenants, six requests of uneven prompt length; every step in a
    ``test.step`` span when ``step_span``.  Returns the engine."""
    eng = Engine(ECFG, executor=exe)
    for t in (0, 1):
        eng.create_ectx(t, SLOPolicy(kv_quota_tokens=128 * 2))
    rng = np.random.RandomState(0)
    for i in range(6):
        eng.submit(Request(i % 2, rng.randint(1, 200, size=9 + 11 * i)
                           .astype(np.int32), max_new_tokens=5))
    while any(r is not None for r in eng.slot_req) or any(
            len(q) for q in eng.queues.values()):
        with (TraceAnnotation(STEP, step=eng.step_count) if step_span
              else contextlib.nullcontext()):
            eng.step()
    return eng


def _streams(eng):
    return sorted((r.rid, r.status.value, tuple(r.generated),
                   tuple(r.chunk_steps), r.start_step, r.finish_step)
                  for r in eng.done)


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_engine_spans_nest_in_their_step(exe, tmp_path):
    _serve(exe)                                  # compile outside the trace
    with profiled(tmp_path) as spans:
        eng = _serve(exe, step_span=True)
    steps = [s for s in spans if s[0] == STEP]
    prog = [s for s in spans if s[0].startswith("osmosis.")]
    assert len(steps) == eng.step_count
    assert {s[0] for s in prog} == SERVE_SPANS
    granted = set()
    for st in steps:
        k = st[3]["step"]
        mine = [s for s in prog if _inside(s, st)]
        assert 3 <= len(mine) <= 9
        # siblings in program order: none overlaps the next
        assert all(a[2] <= b[1] for a, b in zip(mine, mine[1:]))
        names = [s[0] for s in mine]
        assert names[0] == "osmosis.serve.admit"
        assert names[1] == "osmosis.serve.prefill.pack"
        assert names[-1] == "osmosis.serve.account"
        for kind in ("prefill", "decode"):
            if f"osmosis.serve.{kind}.dispatch" in names:
                i = names.index(f"osmosis.serve.{kind}.dispatch")
                assert names[i + 1:i + 3] == [f"osmosis.serve.{kind}.sync",
                                              "osmosis.serve.tokens"]
        for s in mine:
            if s[0] in ("osmosis.serve.admit", "osmosis.serve.prefill.pack",
                        "osmosis.serve.tokens", "osmosis.serve.account"):
                assert s[3]["step"] == k
        admit = mine[0][3]
        if "rids" in admit:
            granted |= {int(x) for x in
                        str(admit["rids"]).strip("[]").split(",")}
    assert all(any(_inside(s, st) for st in steps) for s in prog)
    assert granted == {r.rid for r in eng.done}


def test_prefill_dispatch_carries_rows_and_width(exe, tmp_path):
    """Each prefill call's dispatch span counts the rows it advances (the
    requests its chunk packed) against the program's width."""
    _serve(exe)                                  # compile outside the trace
    with profiled(tmp_path) as spans:
        eng = _serve(exe)
    disp = [s[3] for s in spans if s[0] == "osmosis.serve.prefill.dispatch"]
    packs = [s[3]["rids"] for s in spans
             if s[0] == "osmosis.serve.prefill.pack" and "rids" in s[3]]
    assert len(disp) == len(packs) == eng.prefill_chunks
    assert {int(d["width"]) for d in disp} == {ECFG.prefill_slots_per_step}
    assert [int(d["rows"]) for d in disp] == [
        len(str(r).strip("[]").split(",")) for r in packs]


class _NoSpan(contextlib.nullcontext):
    """What the engine's call sites see with the spans taken out."""

    def __enter__(self):
        return self

    def is_enabled(self):
        return False

    def set_metadata(self, **meta):
        pass


def test_engine_spans_change_no_token(exe, tmp_path, monkeypatch):
    """Profiler off, profiler on, and the spans taken out: one stream."""
    off = _streams(_serve(exe))
    with profiled(tmp_path):
        on = _streams(_serve(exe))
    monkeypatch.setattr(E, "span", lambda *a, **k: _NoSpan())
    bare = _streams(_serve(exe))
    assert off == on == bare
    assert all(status == "done" for _, status, *_ in off)


def _fig9_pair():
    base = get_scenario("fig9_congestor_victim", duration_us=20.0)
    base = dataclasses.replace(base, record_timeline=False)
    return [base.replace(seed=s) for s in (0, 1)]


def _summary(results):
    return [(r.time, {k: np.asarray(v).tolist()
                      for k, v in r.counters.items()},
             [[getattr(s, f) for f in DP.PARITY_STAT_FIELDS]
              for s in r.stats.values()],
             [(e.tenant, e.kind, e.time) for e in r.events])
            for r in results]


def test_sweep_spans_once_per_launch_in_order(tmp_path):
    specs = _fig9_pair()
    DP.run_sweep_specs(specs, precision="fast")  # compile outside the trace
    with profiled(tmp_path) as spans:
        with TraceAnnotation(CALL):
            DP.run_sweep_specs(specs, precision="fast")
    call, = [s for s in spans if s[0] == CALL]
    prog = [s for s in spans if s[0].startswith("osmosis.")]
    assert tuple(s[0] for s in prog) == SWEEP_SPANS
    assert all(_inside(s, call) for s in prog)
    assert all(a[2] <= b[1] for a, b in zip(prog, prog[1:]))
    assert all(s[3]["replicas"] == 2 for s in prog)


def test_sweep_spans_change_no_result(tmp_path, monkeypatch):
    specs = _fig9_pair()
    off = _summary(DP.run_sweep_specs(specs, precision="fast"))
    with profiled(tmp_path):
        on = _summary(DP.run_sweep_specs(specs, precision="fast"))
    monkeypatch.setattr(DP, "span", lambda *a, **k: contextlib.nullcontext())
    bare = _summary(DP.run_sweep_specs(specs, precision="fast"))
    assert off == on == bare


def test_assign_slots_returns_granted_rids():
    """The admit span's ``rids``: ``_assign_slots`` returns the ids it
    granted a slot, in grant order, and never a rejected request's."""
    eng = Engine(ECFG)
    eng.create_ectx(0, SLOPolicy(kv_quota_tokens=128 * 2))
    reqs = [Request(0, np.arange(1, 20, dtype=np.int32), max_new_tokens=4)
            for _ in range(5)]
    reqs.append(Request(3, np.arange(1, 5, dtype=np.int32)))  # no tenant 3
    for r in reqs:
        eng.submit(r)
    granted = eng._assign_slots()
    assert granted == [reqs[0].rid, reqs[1].rid]  # tenant 0's quota
    assert [r.rid for r in reqs if r.slot >= 0] == granted
    assert eng._assign_slots() == []              # no free slot in quota
    assert reqs[-1].status == RequestStatus.REJECTED
    eng.run_until_idle()
    assert all(r.status == RequestStatus.DONE for r in reqs[:5])
