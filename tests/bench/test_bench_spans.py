"""The program's ``osmosis.`` spans and the ``wlbvt_select`` op name on
small hand-made records: the select reader's value, the accepted readers
and ``top_ops`` unmoved by program spans among the host spans, and idle
gaps named after the innermost program span."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench.harness import cell as C  # noqa: E402
from bench.harness import trace as TR  # noqa: E402

# the readers that these hand-made records are built to exercise: each
# reads a value from them
EXERCISED = {
    "sweep": ["sweep.device_ns_per_pkt", "sweep.idle_share",
              "sweep.post_ms_per_scen", "sweep.prep_ms_per_scen",
              "sweep.select_ns_per_pkt"],
    "serve": ["serve.decode_ms", "serve.decode_roofline", "serve.idle_share",
              "serve.prefill_ms", "serve.prefill_roofline",
              "serve.prefill_step_mfu", "serve.queue_p90_ms",
              "serve.sched_ms_per_step", "serve.step_mfu"]}


def _dev(ops, modules=()):
    def cols(evs):
        names = [e[0] for e in evs]
        s = np.array([e[1] for e in evs], float)
        return names, s, s + np.array([e[2] for e in evs], float)
    return {"id": 0, "ops": cols(ops), "modules": cols(modules)}


def sweep_rec():
    """Window 0-1000; one call 100-900 (4 scenarios, 100 packets) whose
    scan runs 205-590 with three select kernels of 10, 10 and 5 ns; a
    fourth kernel at 950 lies outside the call."""
    spans = [("bench.window", 0.0, 1000.0), ("bench.sweep.call", 100.0, 900.0)]
    sel = "%wlbvt_select.6 = (s32[8,128]) custom-call(f32[8,128] %pad.77)"
    ops = [("%while.2 = (s32[]) while(%tuple)", 205, 385),
           (sel, 220, 10), (sel, 240, 10), (sel, 260, 5),
           ("%fusion.3 = s32[8,2] fusion(%p)", 300, 100), (sel, 950, 10)]
    mods = [("jit__launch(1)", 205, 385), ("jit__launch(1)", 950, 10)]
    return {"spans": spans, "devices": [_dev(ops, mods)],
            "surface": {"calls": [{"start": 1.0, "end": 2.0, "scenarios": 4,
                                   "packets": 100}]},
            "device_kind": "TPU v5 lite", "chips": 1}


SWEEP_PROGRAM = [("osmosis.sweep.build", 100.0, 150.0),
                 ("osmosis.sweep.stack", 150.0, 170.0),
                 ("osmosis.sweep.launch", 170.0, 200.0),
                 ("osmosis.sweep.fetch", 200.0, 600.0),
                 ("osmosis.sweep.materialize", 600.0, 900.0)]


def serve_rec():
    """Window 0-1000.  Step 1 (100-400) runs a decode call 150-350 whose
    module runs 180-330; step 2 (420-480) a prefill call 430-470 whose
    module runs 440-460; the engine idles 500-900."""
    spans = [("bench.window", 0.0, 1000.0), ("bench.serve.step", 100.0, 400.0),
             ("bench.serve.decode", 150.0, 350.0),
             ("bench.serve.step", 420.0, 480.0),
             ("bench.serve.prefill", 430.0, 470.0),
             ("bench.serve.idle", 500.0, 900.0)]
    ops = [("%fusion.1 = bf16[8,4096] fusion()", 180, 70),
           ("%while.3 = (s32[]) while()", 250, 80),
           ("%fusion.9 = bf16[8,256,4096] fusion()", 440, 20)]
    mods = [("jit__decode(7)", 180, 150), ("jit__prefill(8)", 440, 20)]
    surface = {
        "steps": [{"start": 1.0, "end": 1.3, "exec": 0.2},
                  {"start": 1.4, "end": 1.5, "exec": 0.05}],
        "calls": [{"kind": "decode", "wall": 0.2, "start": 1.05,
                   "flops": 2e9, "bytes": 9e9},
                  {"kind": "prefill", "wall": 0.05, "start": 1.41,
                   "flops": 8e12, "bytes": 9e9}],
        "queue_ms": [5.0, 7.0, 30.0]}
    return {"spans": spans, "devices": [_dev(ops, mods)], "surface": surface,
            "device_kind": "TPU v5 lite", "chips": 1}


SERVE_PROGRAM = [("osmosis.serve.admit", 100.0, 120.0),
                 ("osmosis.serve.prefill.pack", 120.0, 150.0),
                 ("osmosis.serve.decode.dispatch", 150.0, 170.0),
                 ("osmosis.serve.decode.sync", 170.0, 350.0),
                 ("osmosis.serve.tokens", 350.0, 380.0),
                 ("osmosis.serve.account", 380.0, 400.0),
                 ("osmosis.serve.admit", 420.0, 425.0),
                 ("osmosis.serve.prefill.pack", 425.0, 430.0),
                 ("osmosis.serve.prefill.dispatch", 430.0, 435.0),
                 ("osmosis.serve.prefill.sync", 435.0, 470.0),
                 ("osmosis.serve.tokens", 470.0, 475.0),
                 ("osmosis.serve.account", 475.0, 480.0)]


def with_program(rec, program):
    spans = sorted(rec["spans"] + program, key=lambda s: s[1])
    return {**rec, "spans": spans}


def test_select_reader_hand_value():
    read = C.metric_reader("sweep.select_ns_per_pkt")
    rec = sweep_rec()
    assert read(rec) == pytest.approx((10 + 10 + 5) / 100)
    assert read(with_program(rec, SWEEP_PROGRAM)) == read(rec)
    # a program whose select op has no name reads nothing
    names, s, e = rec["devices"][0]["ops"]
    unnamed = [n.replace("%wlbvt_select", "%custom-call") for n in names]
    rec["devices"][0]["ops"] = (unnamed, s, e)
    assert read(rec) is None


@pytest.mark.parametrize("surface,make,program", [
    ("sweep", sweep_rec, SWEEP_PROGRAM),
    ("serve", serve_rec, SERVE_PROGRAM)])
def test_accepted_readers_ignore_program_spans(surface, make, program):
    """Every per-layer reader of the surface, and ``top_ops``, read the
    same from a record with the program's spans among the host spans as
    from one without (a reader that finds nothing finds nothing in
    both)."""
    bm = C.load_benchmark(ROOT)
    names = [m["name"] for m in bm["per_layer"]
             if m["name"].startswith(surface + ".")]
    assert set(EXERCISED[surface]) <= set(names)
    rec = make()
    both = with_program(rec, program)
    for name in names:
        read = C.metric_reader(name, ROOT)
        assert read(both) == read(rec), name
    for name in EXERCISED[surface]:
        assert C.metric_reader(name, ROOT)(rec) is not None, name
    assert TR.top_ops(both) == TR.top_ops(rec)


def test_idle_gaps_named_after_innermost_program_span():
    rec = with_program(serve_rec(), SERVE_PROGRAM)
    gaps = dict(TR.idle_gaps(rec, k=50))
    # before the decode module: the dispatch (150-170) and the start of
    # the sync (170-180); after it, the rest of the sync (330-350); the
    # engine's own spans of both steps before their first device op
    assert gaps["osmosis.serve.decode.dispatch.head"] == pytest.approx(20e-9)
    assert gaps["osmosis.serve.decode.sync.head"] == pytest.approx(10e-9)
    assert gaps["osmosis.serve.decode.sync.tail"] == pytest.approx(20e-9)
    assert gaps["osmosis.serve.tokens.head"] == pytest.approx(35e-9)
    assert gaps["osmosis.serve.admit.head"] == pytest.approx(25e-9)
    plain = dict(TR.idle_gaps(serve_rec(), k=50))
    assert plain["bench.serve.decode.head"] == pytest.approx(30e-9)
    assert sum(gaps.values()) == pytest.approx(sum(plain.values()))
