"""Whole benchmark runs on the CPU at small sizes, past the chip check,
with the timed path broken underneath: each fault a cell can have, and
the precision control put in the program's place, must turn ``correct``
false under the cell's own limits, and the unbroken path must keep it
true.  The exchange between chips is not a fault of these one-chip
cells."""
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench.harness import cell as C  # noqa: E402
from bench.harness import runner  # noqa: E402


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    """Runs here compile on the CPU: keep JAX's persistent cache off, so
    no test leaves cache settings or entries behind."""
    monkeypatch.setattr(runner, "use_compile_cache", lambda root: "off")


SWEEP = {"traffic": {"replicas": 8, "duration_us": 20.0,
                     "check_replicas": 4}}


def _serve_overrides():
    tiny = {"hidden_size": 64, "intermediate_size": 128,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
            "engine": {"max_slots": 4, "max_len": 256, "prefill_chunk": 64,
                       "prefill_slots_per_step": 2, "scheduler": "wlbvt",
                       "arbiter": "dwrr"}}
    mix = json.loads((ROOT / "bench/traffic/chat3.json").read_text())
    for t in mix["tenants"]:
        t["kv_slots"] = 1
        t["prompt"] = {"median": 40, "sigma": 0.8, "min": 4, "max": 150}
        t["output"] = {"median": 8, "sigma": 0.7, "min": 2, "max": 32}
    return {"config": tiny,
            "traffic": {"rate_per_s": 8.0, "max_total_tokens": 256,
                        "tenants": mix["tenants"], "check_tokens": 200}}


def _run(cell, overrides, seed=2 ** 31 + 3):
    import jax
    return runner.run_cell(cell, seed, 2.0, False,
                           t_start=time.perf_counter(), overrides=overrides,
                           devices=lambda n: jax.devices()[:n])


def test_sweep_sound_run_is_correct():
    out = _run("sweep.fig9.r256", SWEEP)
    assert out["correct"] is True
    assert out["metrics"]["sweep_scen_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"


def _alter_answer(monkeypatch):
    import repro.sim.devicepath as DP
    orig = DP._materialize

    def altered(*a, **kw):
        res = orig(*a, **kw)
        res.stats[0].completed += 50
        return res
    monkeypatch.setattr(DP, "_materialize", altered)


def _state_unchanged(monkeypatch):
    import repro.sim.devicepath as DP

    def build(T, P, C, S, sched, impl):
        def launch(state, data):
            R = state["now"].shape[0]
            z = np.zeros((S, R), state["now"].dtype)
            return state, (np.zeros((S, R), np.int32), z,
                           np.full((S, R), -1, np.int32), z)
        return launch
    monkeypatch.setattr(DP, "_build_launch", build)


def _half_batch(monkeypatch):
    import repro.sim.devicepath as DP
    orig = DP.run_sweep_specs

    def half(specs, **kw):
        got = orig(specs[:len(specs) // 2], **kw)
        return got + got[:len(specs) - len(got)]
    monkeypatch.setattr(DP, "run_sweep_specs", half)


@pytest.mark.parametrize("fault", [_alter_answer, _state_unchanged,
                                   _half_batch])
def test_sweep_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    assert _run("sweep.fig9.r256", SWEEP)["correct"] is False


def test_serve_sound_run_is_correct():
    out = _run("serve.qwen3-8b-l16.chat3", _serve_overrides())
    assert out["correct"] is True
    assert out["attempted"] == 16 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_mean_ms", "itl_p50_ms",
                                   "itl_p95_ms", "out_tok_per_s", "setup_s"}


def _token_altered(monkeypatch):
    from repro.serving.engine import ModelExecutor
    orig = ModelExecutor.decode

    def decode(self, tokens, lengths, active):
        nxt = orig(self, tokens, lengths, active).copy()
        nxt[active] = (nxt[active] + 1) % 512
        return nxt
    monkeypatch.setattr(ModelExecutor, "decode", decode)


def _cache_unchanged(monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.serving.engine import ModelExecutor

    def decode(self, tokens, lengths, active):
        copy = jax.tree.map(jnp.copy, self.cache)
        nxt, _, _ = self.fns.decode(self.params, copy, jnp.asarray(tokens),
                                    jnp.asarray(lengths), jnp.asarray(active))
        return np.asarray(nxt)
    monkeypatch.setattr(ModelExecutor, "decode", decode)


def _half_prefill(monkeypatch):
    from repro.serving.engine import ModelExecutor
    orig = ModelExecutor.prefill

    def prefill(self, tokens, lengths, valid_n):
        valid_n = valid_n.copy()
        valid_n[:len(valid_n) // 2] = 0
        return orig(self, tokens, lengths, valid_n)
    monkeypatch.setattr(ModelExecutor, "prefill", prefill)


@pytest.mark.parametrize("fault", [_token_altered, _cache_unchanged,
                                   _half_prefill])
def test_serve_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    assert _run("serve.qwen3-8b-l16.chat3", _serve_overrides())["correct"] \
        is False


def _deep_serve_overrides():
    """The cell's 16 layers at a small width: deep enough that the float8
    control reads far above the limit of ``correct``."""
    over = _serve_overrides()
    over["config"].update(hidden_size=128, intermediate_size=256,
                          num_hidden_layers=16, head_dim=32)
    return over


def test_serve_sound_run_at_control_size_is_correct():
    out = _run("serve.qwen3-8b-l16.chat3", _deep_serve_overrides())
    assert out["correct"] is True


def _patch_surface(monkeypatch, patch):
    """Each surface module as the harness loads it, with ``patch`` applied
    to it before the run uses it."""
    orig = C.surface

    def surface(name, root=C.ROOT):
        mod = orig(name, root)
        patch(mod)
        return mod
    monkeypatch.setattr(C, "surface", surface)


def test_sweep_bf16_control_is_not_correct(monkeypatch):
    """The reference in bfloat16 answers every launch of the window in the
    program's place; the harness compares it as it would the program."""
    import ml_dtypes

    def control(mod):
        def launch(self):
            return [mod.REF.simulate(self.nic, self.scenario, s,
                                     dtype=ml_dtypes.bfloat16)
                    for s in self.seeds]
        mod.Cell._launch = launch
        mod.program_row = lambda res: res
        mod.packets = lambda results: int(sum(r["arrivals"].sum()
                                              for r in results))
    _patch_surface(monkeypatch, control)
    out = _run("sweep.fig9.r256", SWEEP)
    assert out["correct"] is False
    assert out["checks"]["end_time"]["value"] > \
        out["checks"]["end_time"]["limit"]


def test_serve_fp8_control_is_not_correct(monkeypatch):
    """At each position of the served requests the float8 reference's
    first token stands in for the served one; the harness compares it
    with the f32 reference as it would the program's tokens.  Over 2
    layers at a small width float8 moves the logits too little to read;
    over 16, sound runs read under 0.002 and the control 0.10-0.16 on
    four seeds."""
    def control(mod):
        orig = mod.Cell.gaps

        def gaps(self, picked, fp8=False):
            ref, _ = orig(self, picked)
            ctl, _ = orig(self, picked, fp8=True)
            return ref, [c.argmax(axis=-1) for c in ctl]
        mod.Cell.gaps = gaps
    _patch_surface(monkeypatch, control)
    out = _run("serve.qwen3-8b-l16.chat3", _deep_serve_overrides())
    assert out["correct"] is False
    assert out["checks"]["mean_gap"]["value"] > \
        out["checks"]["mean_gap"]["limit"]
