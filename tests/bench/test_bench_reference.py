"""The plain references against the program at small sizes on the CPU,
and the precision controls that the comparisons must reject."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench.harness import cell as C  # noqa: E402
from bench.harness import traffic as TF  # noqa: E402
from bench.reference import pspin as PREF  # noqa: E402
from bench.reference import qwen3 as QREF  # noqa: E402
from bench.surfaces import sweep as SW  # noqa: E402

NIC = C.load_json(ROOT / "bench/configs/pspin-32pu-400g.json")
FIG9 = C.load_json(ROOT / "bench/traffic/fig9-r256.json")
FLOOD = C.load_json(ROOT / "bench/traffic/flood128-p64.json")
KILLS = {**FIG9, "duration_us": 20.0, "fifo_capacity": 64,
         "tenants": [{**FIG9["tenants"][0], "kernel_cycle_limit": 500,
                      "priority": 2.0},
                     {**FIG9["tenants"][1], "total_cycle_limit": 200000,
                      "seed_offset": 3}]}
MIXES = {
    "fig9": {**FIG9, "duration_us": 30.0},
    "fig9_rr": {**FIG9, "duration_us": 30.0, "scheduler": "rr"},
    "flood": {**FLOOD, "duration_us": 4.0, "horizon_us": 4.0},
    "kills": KILLS,
}


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_pspin_reference_equals_host_oracle(mix):
    """The plain event loop gives the program's f64 host oracle's answers
    exactly, for every seed form the benchmark uses."""
    from repro.sim.devicepath import host_oracle
    m = MIXES[mix]
    sc = TF.sweep_scenario(m)
    for spec in SW.program_specs(NIC, m, [0, 2 ** 31 + 7]):
        ref = PREF.simulate(NIC, sc, spec.seed)
        h = host_oracle(spec, record_completions=False)
        assert h.time == ref["time"]
        for i in range(len(sc["tenants"])):
            st = h.stats[i]
            assert (st.completed, st.killed, st.drops) == \
                (ref["completed"][i], ref["killed"][i], ref["drops"][i])
            assert st.kernel_time_sum == ref["kernel_time_sum"][i]
            assert st.served_payload_bytes == ref["served_payload_bytes"][i]
            assert h.telemetry.counter("ecn_marks")[i] == ref["ecn_marks"][i]
        if mix == "kills":
            assert ref["killed"].sum() > 0 and ref["drops"].sum() > 0


def test_sweep_f32_path_within_limits_and_bf16_control_outside():
    from repro.sim.devicepath import run_sweep_specs
    m = MIXES["kills"]
    sc = TF.sweep_scenario(m)
    specs = SW.program_specs(NIC, m, [11, 12])
    for spec, res in zip(specs, run_sweep_specs(specs, precision="fast")):
        ref = PREF.simulate(NIC, sc, spec.seed)
        got = SW.compare(SW.program_row(res), ref)
        assert all(got[k] <= SW.LIMITS[k] for k in SW.LIMITS), got
        import ml_dtypes
        ctl = SW.compare(PREF.simulate(NIC, sc, spec.seed,
                                       dtype=ml_dtypes.bfloat16), ref)
        assert any(ctl[k] > SW.LIMITS[k] for k in SW.LIMITS), ctl


TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 1000000.0,
        "attention_bias": False, "hidden_act": "silu",
        "tie_word_embeddings": False, "program_preset": "qwen3-8b"}


def _program(c, seed):
    from bench.surfaces.serve import model_config, program_params
    from repro.serving.serve_step import build_serve_fns
    cfg = model_config(c)
    fns = build_serve_fns(cfg, None, batch=1, max_len=64, prefill_chunk=64)
    return cfg, fns, program_params(c, fns, seed)


def test_program_gets_the_reference_weights():
    c = {**TINY, "torch_dtype": "bfloat16"}
    _, _, params = _program(c, 5)
    g = params["groups"][0]
    for l in range(c["num_hidden_layers"]):
        w = QREF.layer_weights(c, QREF.root_key(5), l, np.dtype("bfloat16"))
        assert (np.asarray(g["mixer"]["wq"][l]) == np.asarray(w["q_proj"])).all()
        assert (np.asarray(g["mlp"]["w_down"][l])
                == np.asarray(w["down_proj"])).all()
        assert (np.asarray(g["norm2"][l])
                == np.asarray(w["post_attention_layernorm"])).all()


def test_qwen3_reference_matches_program_forward_in_f32():
    """At float32 the program's full-sequence forward and the plain
    reference agree to rounding: the layer equations are the same."""
    import jax.numpy as jnp
    from repro.models.transformer import forward, make_positions
    c = {**TINY, "torch_dtype": "float32"}
    cfg, _, params = _program(c, 9)
    rng = np.random.default_rng(0)
    seq = rng.integers(1, c["vocab_size"], size=40).astype(np.int32)
    got, _, _ = forward(params, cfg, jnp.asarray(seq[None]),
                        make_positions(cfg, 1, len(seq)))
    (ref,) = QREF.logits(c, 9, [seq], [np.arange(len(seq))],
                         dtype="float32")
    got = np.asarray(got[0])
    assert np.abs(got - ref).max() <= 1e-4 * np.abs(ref).max()
    assert (got.argmax(-1) == ref.argmax(-1)).all()


def test_fp8_control_departs_from_reference():
    """The control (float8 projections) puts other tokens first and its
    gaps far exceed the reference's own rounding."""
    c = {**TINY, "torch_dtype": "bfloat16"}
    rng = np.random.default_rng(1)
    seqs = [rng.integers(1, 512, size=n).astype(np.int32) for n in (60, 90)]
    pos = [np.arange(10, len(s)) for s in seqs]
    ref = QREF.logits(c, 3, seqs, pos)
    ctl = QREF.logits(c, 3, seqs, pos, fp8=True)
    widest = max(float(QREF.served_gaps(r, k.argmax(-1)).max())
                 for r, k in zip(ref, ctl))
    assert widest > 0.0
    assert all((QREF.served_gaps(r, r.argmax(-1)) == 0).all() for r in ref)
