"""``chip_smoke.py`` on the CPU at small sizes.

The script itself refuses to run without a TPU; these tests drive its
phases directly at reduced sizes so that a change to the sweep or serving
API breaks here, not on the chip.  The Pallas kernel is not compiled on
the CPU (the auto impl picks ``jnp``), so the ``tpu_custom_call`` check is
the one part left to the chip and to ``test_chip_compile.py``.
"""
import dataclasses
import importlib.util
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _small_qwen3(num_layers: int):
    """Qwen3 family at smoke widths, bf16 weights, vocab divisible by 4."""
    from repro.configs import smoke_config
    return dataclasses.replace(smoke_config("qwen3-8b"),
                               num_layers=num_layers, vocab_size=512,
                               param_dtype="bfloat16")


def test_refuses_without_tpu(smoke, monkeypatch, capsys):
    import repro.compile_cache
    monkeypatch.setattr(repro.compile_cache, "use_persistent_cache",
                        lambda: "unused")
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err


def test_sweep_phase(smoke, monkeypatch):
    monkeypatch.setattr(smoke, "require_kernel", lambda text: None)
    res = smoke.sweep_phase(replicas=8, sample=2, exact_replicas=2,
                            params={"duration_us": 20.0})
    assert res["exact_parity"] is True     # f64 on the CPU is bit-exact


def test_serve_phase(smoke):
    res = smoke.serve_phase(_small_qwen3(2), seed=0)
    assert res["logits_rel_l2"] <= smoke.LOGITS_REL_L2


def test_four_chip_phase(smoke, monkeypatch):
    """On 4 of the forced host devices: sharded serving, weight
    split and sharded-vs-unsharded logits."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices")
    monkeypatch.setattr(smoke, "serve_config", _small_qwen3)
    monkeypatch.setattr(smoke, "get_full_layers", lambda: 4)
    monkeypatch.setattr(smoke, "SERVE_LAYERS_ONE_CHIP", 2)
    smoke.four_chip_phase(seed=0)
