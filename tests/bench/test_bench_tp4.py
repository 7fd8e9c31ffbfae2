"""The four-chip cell ``serve.qwen3-8b-tp4.chat3`` on 4 of the test
session's 8 virtual CPU devices, at a small width whose head counts the
4-way ``model`` split divides (8 query heads, 4 key/value heads): a
sound run is ``correct``, a run with the exchange between chips left out
is not, and the sharded program's prefill-then-decode logits agree with
the plain reference.  Then the two exchange readers on hand-made
four-device records."""
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench.harness import cell as C  # noqa: E402
from bench.harness import collectives as CO  # noqa: E402
from bench.harness import runner  # noqa: E402

CELL = "serve.qwen3-8b-tp4.chat3"
CHIPS = 4
TINY = {"hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
        "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 16,
        "vocab_size": 512}


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    """Runs here compile on the CPU: keep JAX's persistent cache off, so
    no test leaves cache settings or entries behind."""
    monkeypatch.setattr(runner, "use_compile_cache", lambda root: "off")


def _overrides():
    mix = json.loads((ROOT / "bench/traffic/chat3-tp4.json").read_text())
    for t in mix["tenants"]:
        t["kv_slots"] = 1
        t["prompt"] = {"median": 40, "sigma": 0.8, "min": 4, "max": 150}
        t["output"] = {"median": 8, "sigma": 0.7, "min": 2, "max": 32}
    engine = {"max_slots": 4, "max_len": 256, "prefill_chunk": 64,
              "prefill_slots_per_step": 2, "scheduler": "wlbvt",
              "arbiter": "dwrr"}
    return {"config": {**TINY, "engine": engine},
            "traffic": {"rate_per_s": 8.0, "max_total_tokens": 256,
                        "tenants": mix["tenants"], "check_tokens": 200}}


def _run(trace=False, seed=2 ** 31 + 5):
    import jax
    return runner.run_cell(CELL, seed, 2.0, trace,
                           t_start=time.perf_counter(), overrides=_overrides(),
                           devices=lambda n: jax.devices()[:n])


def test_tp4_cell_runs_on_four_devices_and_is_correct():
    bm = C.load_benchmark(ROOT)
    assert C.find_workload(bm, CELL)["chips"] == CHIPS
    out = _run()
    assert out["correct"] is True
    assert out["device"]["count"] == CHIPS
    assert out["attempted"] == 16 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_mean_ms", "itl_p50_ms",
                                   "itl_p95_ms", "out_tok_per_s", "setup_s"}


def test_tp4_exchange_left_out_is_not_correct(monkeypatch):
    """Chips 1-3 hold zeros for their rows of the row-parallel ``wo`` and
    ``w_down``: the all-reduce after each then carries chip 0's partial
    sum alone into the residual stream, as if the exchange were left
    out."""
    import jax
    from bench.surfaces import serve

    orig = serve.program_params

    def chip0_only(c, fns, seed):
        params = orig(c, fns, seed)
        layer = params["groups"][0]
        for part, name in (("mixer", "wo"), ("mlp", "w_down")):
            w = layer[part][name]
            rows = w.shape[1] // CHIPS
            layer[part][name] = jax.device_put(w.at[:, rows:].set(0),
                                               w.sharding)
        return params
    monkeypatch.setattr(serve, "program_params", chip0_only)
    out = _run()
    assert out["correct"] is False
    assert out["checks"]["mean_gap"]["value"] > \
        out["checks"]["mean_gap"]["limit"]


def test_tp4_traced_run_profiles_the_last_slice(monkeypatch):
    """A traced run profiles the window's last ``TRACE_SECONDS``: the
    traced window is that slice, not the whole 2 s window.  (No per-layer
    metric is read: the CPU has no peaks and no device ops.)"""
    orig = C.surface

    def surface(name, root=C.ROOT):
        mod = orig(name, root)
        mod.TRACE_SECONDS = 0.5
        return mod
    monkeypatch.setattr(C, "surface", surface)
    monkeypatch.setattr(C, "per_layer", lambda bm, cell: [])
    out = _run(trace=True)
    assert out["correct"] is True
    assert 0.3 < out["device"]["window_s"] < 1.5


def _sharded_logits(dtype: str, seed: int):
    """Prefill a 40-token prompt in two chunks of 32, then decode 3
    greedy tokens, on a (data=1, model=4) mesh; the logits at positions
    39-42 and the sequence they were computed over."""
    import jax
    import jax.numpy as jnp
    from bench.surfaces import serve
    from repro.launch.mesh import make_mesh
    from repro.serving.serve_step import build_serve_fns

    c = {**C.load_json(ROOT / "bench/configs/qwen3-8b-tp4.json"), **TINY,
         "torch_dtype": dtype}
    mesh = make_mesh((1, CHIPS), ("data", "model"),
                     devices=jax.devices()[:CHIPS])
    fns = build_serve_fns(serve.model_config(c), mesh, batch=2, max_len=128,
                          prefill_chunk=32)
    params = serve.program_params(c, fns, seed)
    cache = fns.init_cache()
    prompt = np.random.default_rng(seed).integers(1, c["vocab_size"], 40)
    rows, seq = [], list(prompt)
    for start in (0, 32):
        chunk = np.zeros((2, 32), np.int32)
        part = prompt[start:start + 32]
        chunk[0, :len(part)] = part
        nxt, last, cache = fns.prefill_chunk(
            params, cache, jnp.asarray(chunk),
            jnp.asarray([start, 0], jnp.int32),
            jnp.asarray([len(part), 0], jnp.int32))
    rows.append(np.asarray(last[0], np.float32))
    length = len(prompt)
    for _ in range(3):
        tok = int(nxt[0])
        seq.append(tok)
        nxt, last, cache = fns.decode(
            params, cache, jnp.asarray([tok, 0], jnp.int32),
            jnp.asarray([length, 0], jnp.int32),
            jnp.asarray([True, False]))
        rows.append(np.asarray(last[0], np.float32))
        length += 1
    return c, np.stack(rows), np.asarray(seq, np.int32)


def _gap_to_reference(dtype: str, seed: int) -> float:
    """Largest logit difference from the f32 reference's full forward
    over the same (dtype-rounded) weights, over the logits' spread."""
    from bench.reference import qwen3 as REF
    c, got, seq = _sharded_logits(dtype, seed)
    ref = REF.logits(c, seed, [seq], [np.arange(39, 43)], dtype=dtype)[0]
    return float(np.abs(got - ref).max() / ref.std())


def test_tp4_sharded_logits_agree_with_reference():
    """In f32 the sharded program differs from the reference only in the
    order of its sums (the partial sums of each row-parallel matmul are
    added across the chips): ~1e-6 of the logits' spread.  The limit,
    1e-3, is far below what bf16 activations give (each op rounds to
    2^-8, ~4e-3 relative), and the bf16 program over the same rounded
    weights fails it."""
    seed = 2 ** 33 + 7
    assert _gap_to_reference("float32", seed) < 1e-3
    assert _gap_to_reference("bfloat16", seed) > 1e-3


# ---------------------------------------------------------------------------
# exchange readers on hand-made four-device records
# ---------------------------------------------------------------------------
AR_START = "%all-reduce-start.1 = bf16[8,1,4096]{2,0,1} all-reduce-start(%f)"
AR_DONE = "%all-reduce-done.1 = bf16[8,1,4096]{2,0,1} all-reduce-done(%s)"
AR = ("%all-reduce.5 = bf16[8,1,4096]{2,0,1:T(8,128)(2,1)S(1)} "
      "all-reduce(%fusion.189), channel_id=3, replica_groups=[1,4]<=[4]")
ACS = ("%async-collective-start = (pred[8,512]{1,0}, pred[8,2048]{1,0}) "
       "fusion(%get-tuple-element.673), kind=kCustom, "
       "calls=%fused_computation.159")
ACD = ("%async-collective-done = pred[8,2048]{1,0} fusion(%g), "
       "kind=kCustom, calls=%fused_computation.163")
ACF = ("%fusion.195 = (f32[8,8]{0,1}, bf16[8,8,128]{2,0,1}) fusion(%p), "
       "kind=kOutput, calls=%async_collective_fusion.195")
COMPUTE = "%fusion.179 = bf16[8,256,12288]{2,1,0} fusion(%p, %q), kind=kOutput"


def _dev(i, ops, modules):
    def cols(evs):
        s = np.array([e[1] for e in evs], float)
        return [e[0] for e in evs], s, s + np.array([e[2] for e in evs],
                                                    float)
    return {"id": i, "ops": cols(ops), "modules": cols(modules)}


def exchange_rec(with_collectives=True):
    """Window 0-1000 on four chips.  Decode modules 100-300 and 500-700,
    a prefill module 800-900, a decode module at 1100 past the window.
    Chip ``k``'s first decode call holds an all-reduce's ``-start`` at
    150-170 and ``-done`` at 160-190+k (union 40+k), its second a sync
    all-reduce of 10; the prefill an async collective's start (820-830)
    and done (825-845), union 25, and a fusion around it (830-850) that
    is not counted; an all-reduce at 400-420 and one in the module past
    the window lie outside both."""
    devs = []
    for k in range(CHIPS):
        ops = [(COMPUTE, 110, 30), (COMPUTE, 510, 30), (COMPUTE, 805, 10)]
        if with_collectives:
            ops += [(AR_START, 150, 20), (AR_DONE, 160, 30 + k),
                    (AR, 550, 10), (ACS, 820, 10), (ACD, 825, 20),
                    (ACF, 830, 20), (AR, 400, 20), (AR, 1110, 10)]
        mods = [("jit__decode(12)", 100, 200), ("jit__decode(12)", 500, 200),
                ("jit__prefill(13)", 800, 100),
                ("jit__decode(12)", 1100, 50)]
        devs.append(_dev(k, ops, mods))
    return {"spans": [("bench.window", 0.0, 1000.0)], "devices": devs,
            "surface": {}, "device_kind": "TPU v5 lite", "chips": CHIPS}


def test_exchange_readers_hand_values():
    rec = exchange_rec()
    decode = C.metric_reader("serve.decode_exchange_ms", ROOT)(rec)
    prefill = C.metric_reader("serve.prefill_exchange_ms", ROOT)(rec)
    # per chip (40 + k + 10) over 2 calls, averaged over k = 0..3
    assert decode == pytest.approx((50 + 1.5) / 2 * 1e-6)
    assert prefill == pytest.approx(25 * 1e-6)


def test_exchange_readers_leave_out_ops_outside_the_modules():
    rec = exchange_rec()
    base = CO.exchange_ms(rec, "decode")
    for d in rec["devices"]:
        names, s, e = d["ops"]
        inside = [not (a in (400.0, 1110.0)) for a in s]
        d["ops"] = ([n for n, k in zip(names, inside) if k], s[inside],
                    e[inside])
    assert CO.exchange_ms(rec, "decode") == base


def test_exchange_readers_read_none_without_collectives():
    rec = exchange_rec(with_collectives=False)
    for name in ("serve.decode_exchange_ms", "serve.prefill_exchange_ms"):
        assert C.metric_reader(name, ROOT)(rec) is None


@pytest.mark.parametrize("name,is_exchange", [
    (AR, True), (AR_START, True), (AR_DONE, True), (ACS, True), (ACD, True),
    (ACF, False),
    ("%all-gather.4 = f32[4,1,8]{2,1,0} all-gather(%bitcast.271), "
     "dimensions={0}", True),
    ("%collective-permute.2 = f32[8]{0} collective-permute(%x)", True),
    (COMPUTE, False),
    ("%while.2 = (s32[], bf16[8,1,4096]{2,0,1}) while(%tuple), "
     "condition=%cond, body=%wide.region_0.10_spmd.sunk", False),
    ("%gather_fusion.3 = bf16[8,4096]{1,0} fusion(%a, %b), kind=kLoop, "
     "metadata={op_name=\"jit(_decode)/all_gather\"}", False)])
def test_collective_names(name, is_exchange):
    assert CO.is_collective(name) is is_exchange
