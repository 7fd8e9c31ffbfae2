"""Per-architecture smoke tests + model-level invariants.

Every assigned arch instantiates a REDUCED same-family config and runs
one forward/train step on CPU asserting output shapes + finiteness; the
chunked-prefill == single-shot exactness test covers the serving path for
all block kinds (attention/local/MLA/SSD/RG-LRU).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _kv import token_major
from repro.configs import (SHAPES, cell_supported, get_config, list_archs,
                           param_count, smoke_config)
from repro.models.registry import build_model, input_names

ARCHS = list_archs()


def _train_batch(cfg, B=2, S=24):
    batch = {"tokens": jnp.ones((B, S), jnp.int32),
             "labels": jnp.ones((B, S), jnp.int32)}
    names = input_names(cfg, "train")
    if "frames" in names:
        batch["frames"] = jnp.zeros((B, cfg.num_audio_frames, cfg.d_model),
                                    jnp.float32)
    if "vis_embeds" in names:
        batch["vis_embeds"] = jnp.zeros((B, S, cfg.d_model), jnp.float32)
        batch["vis_mask"] = jnp.zeros((B, S), bool)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_shapes_and_finite(arch):
    cfg = smoke_config(arch)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = _train_batch(cfg)
    logits, aux = m.forward(params, batch)
    B, S = batch["tokens"].shape
    assert logits.shape == (B, S, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step_decreases_nothing_nan(arch):
    from repro.training.trainer import build_trainer
    cfg = smoke_config(arch)
    tr = build_trainer(cfg, total_steps=10, donate=False)
    state = tr.init_state(jax.random.PRNGKey(0))
    batch = _train_batch(cfg, B=2, S=16)
    state, metrics = tr.train_step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert int(state.step) == 1


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode_path(arch):
    cfg = smoke_config(arch)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, max_len = 2, 48
    cache = m.init_cache(B, max_len)
    lengths = jnp.zeros((B,), jnp.int32)
    kw = {}
    if cfg.is_encoder_decoder:
        kw["frames"] = jnp.zeros((B, cfg.num_audio_frames, cfg.d_model),
                                 jnp.float32)
    logits, cache = m.prefill(params, jnp.ones((B, 8), jnp.int32), cache,
                              lengths, **kw)
    logits, cache = m.decode_step(params, jnp.ones((B, 1), jnp.int32),
                                  cache, lengths + 8)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", ARCHS)
def test_param_count_matches_init(arch):
    """The analytic param_count used for rooflines must equal the real
    initialized tree exactly."""
    cfg = smoke_config(arch)
    m = build_model(cfg)
    sds = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    actual = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(sds))
    assert actual == param_count(cfg), (arch, actual, param_count(cfg))


@pytest.mark.parametrize("arch", ["qwen3-8b", "gemma2-27b", "mamba2-370m",
                                  "recurrentgemma-2b",
                                  "deepseek-v2-lite-16b"])
def test_chunked_prefill_matches_single_shot(arch):
    """Ragged chunked prefill (the OSMOSIS fragmentation data plane) is
    exact: same last logits and same post-prefill decode as one shot."""
    cfg = smoke_config(arch)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, P, C, max_len = 2, 23, 8, 64
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0,
                              cfg.vocab_size)
    cache_a = m.init_cache(B, max_len)
    logits_a, cache_a = m.prefill(params, toks, cache_a,
                                  jnp.zeros(B, jnp.int32))
    cache_b = m.init_cache(B, max_len)
    lengths = jnp.zeros(B, jnp.int32)
    off = 0
    while off < P:
        n = min(C, P - off)
        chunk = jnp.zeros((B, C), jnp.int32).at[:, :n].set(
            toks[:, off:off + n])
        valid = jnp.broadcast_to(jnp.arange(C)[None] < n, (B, C))
        logits_b, cache_b = m.prefill(params, chunk, cache_b, lengths,
                                      valid=valid)
        lengths = lengths + n
        off += n
    err = float(jnp.max(jnp.abs(logits_a[:, -1] - logits_b[:, n - 1])))
    assert err < 5e-3, err
    nxt = jnp.argmax(logits_a[:, -1], -1)[:, None].astype(jnp.int32)
    la, _ = m.decode_step(params, nxt, cache_a, jnp.full(B, P, jnp.int32))
    lb, _ = m.decode_step(params, nxt, cache_b, lengths)
    assert float(jnp.max(jnp.abs(la - lb))) < 5e-3


def test_local_attention_ring_cache_is_o_window():
    """Gemma2-style local layers keep O(window) cache regardless of
    context (DESIGN.md long-context claim)."""
    cfg = smoke_config("gemma2-27b")
    m = build_model(cfg)
    cache = m.init_cache(2, 1024)
    leaves = jax.tree.leaves(token_major(cache))
    # at least one leaf (local layers) capped at window, one at 1024
    sizes = {x.shape[-3] if x.ndim >= 3 else x.shape[-1] for x in leaves
             if x.ndim >= 2}
    assert cfg.window_size in sizes or any(
        s <= cfg.window_size for s in sizes)


def test_long_context_skip_rules():
    """long_500k runs only for sub-quadratic archs (DESIGN.md §5)."""
    allowed = {a for a in ARCHS
               if cell_supported(get_config(a), SHAPES["long_500k"])[0]}
    assert allowed == {"mamba2-370m", "recurrentgemma-2b", "gemma2-27b"}
    for a in ARCHS:
        ok, reason = cell_supported(get_config(a), SHAPES["train_4k"])
        assert ok, (a, reason)


def test_moe_ragged_matches_gshard_when_no_drops():
    """With generous capacity the two MoE dispatch impls agree."""
    import dataclasses
    from repro.models import moe as M
    cfg = smoke_config("deepseek-v2-lite-16b")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0),
        dtype="float32", param_dtype="float32")
    params = M.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.float32)
    y1, a1 = M.apply_moe(params, x, cfg, "gshard")
    y2, a2 = M.apply_moe(params, x, cfg, "ragged")
    assert float(jnp.max(jnp.abs(y1 - y2))) < 1e-4
    assert float(jnp.abs(a1 - a2)) < 1e-5


def test_mla_absorbed_decode_matches_expanded():
    """DeepSeek MLA: the absorbed (latent MQA) decode path must agree with
    the expanded training path on the same tokens.  fp32: in bf16 the two
    contraction orders legitimately diverge (documented in DESIGN.md)."""
    import dataclasses
    cfg = dataclasses.replace(smoke_config("deepseek-v2-lite-16b"),
                              dtype="float32")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, S = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                              cfg.vocab_size)
    logits_train, _ = m.forward(params, {"tokens": toks})
    cache = m.init_cache(B, 32)
    logits_serve, _ = m.prefill(params, toks, cache,
                                jnp.zeros(B, jnp.int32))
    err = float(jnp.max(jnp.abs(logits_train - logits_serve)))
    assert err < 5e-3, err


def test_moe_grouped_dispatch_padding_exact():
    """Grouped gshard with T not divisible by group_size: padded tokens
    are dropped (keep=False) and outputs match the ungrouped semantics."""
    import dataclasses
    from repro.models import moe as M
    cfg = dataclasses.replace(
        smoke_config("deepseek-v2-lite-16b"), dtype="float32",
        param_dtype="float32")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = M.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 21, cfg.d_model),
                          jnp.float32)   # T=42, group 16 -> pad 6
    y_small, _ = M.apply_moe_gshard(params, x, cfg, group_size=16)
    y_big, _ = M.apply_moe_gshard(params, x, cfg, group_size=4096)
    # generous capacity => no drops in either grouping => identical
    assert float(jnp.max(jnp.abs(y_small - y_big))) < 1e-4


@pytest.mark.parametrize("T,S,stacked", [
    (16, 1, False), (16, 1, True), (16, 5, False), (16, 5, True),
    (16, 16, True), (16, 23, False), (8, 3, True)])
def test_ring_put_writes_each_entry_at_offset_mod_length(T, S, stacked):
    """The KV cache write against a plain loop: entry i of row b lands at
    (offset_b + i) mod T, in order, where it is valid; a one-entry write
    lands whatever its validity.  Rows wrap the ring's end, start on it,
    stay inside it, and (S > T) go round it more than once; with a layer
    stack only the indexed layer changes."""
    from repro.models.attention import _ring_put
    rng = np.random.default_rng(T * 100 + S)
    B, H, D, G, layer = 3, 2, 4, 3, 1
    offsets = np.asarray([T - 2, 0, 3 * T + 5], np.int32)
    valid = np.arange(S)[None] < np.asarray([S, max(S - 2, 1), 0])[:, None]
    for shape, axis in (((B, H, T, D), 2), ((B, T), 1)):
        buf = rng.normal(size=((G,) + shape) if stacked else shape)
        new = rng.normal(size=shape[:axis] + (S,) + shape[axis + 1:])
        want = buf.copy()
        one = want[layer] if stacked else want
        for b in range(B):
            for i in range(S):
                if S == 1 or valid[b, i]:
                    idx = [b] + [slice(None)] * (len(shape) - 1)
                    src = list(idx)
                    idx[axis] = (offsets[b] + i) % T
                    src[axis] = i
                    one[tuple(idx)] = new[tuple(src)]
        got = _ring_put(jnp.asarray(buf, jnp.float32),
                        jnp.asarray(new, jnp.float32), jnp.asarray(offsets),
                        jnp.asarray(valid), axis,
                        jnp.int32(layer) if stacked else None)
        np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-6)


def test_encdec_served_chunks_match_the_forward():
    """Whisper's decoder served from its cache (the encoder's K/V filled
    by the first chunk, then a ragged chunk and three decode steps) gives
    the logits of the forward over the whole sequence."""
    import dataclasses
    cfg = dataclasses.replace(smoke_config("whisper-large-v3"),
                              dtype="float32")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, C = 2, 8
    frames = jax.random.normal(jax.random.PRNGKey(1),
                               (B, cfg.num_audio_frames, cfg.d_model))
    seq = jax.random.randint(jax.random.PRNGKey(2), (B, 16), 0,
                             cfg.vocab_size)
    want, _ = m.forward(params, {"frames": frames, "tokens": seq})
    cache = m.init_cache(B, 32)
    lengths = jnp.zeros(B, jnp.int32)
    got, cache = m.prefill(params, seq[:, :C], cache, lengths, frames=frames)
    chunk = jnp.zeros((B, C), jnp.int32).at[:, :5].set(seq[:, C:C + 5])
    valid = jnp.broadcast_to(jnp.arange(C)[None] < 5, (B, C))
    logits, cache = m.prefill(params, chunk, cache, lengths + C, valid=valid)
    got = [got[:, -1], logits[:, 4]]
    for n in range(C + 5, 16):
        logits, cache = m.decode_step(params, seq[:, n:n + 1], cache,
                                      jnp.full(B, n, jnp.int32))
        got.append(logits[:, 0])
    want = np.asarray(want[:, [C - 1] + list(range(C + 4, 16))])
    np.testing.assert_allclose(np.stack(got, 1), want, rtol=0,
                               atol=1e-4 * np.abs(want).max())
