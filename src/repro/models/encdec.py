"""Encoder-decoder backbone (whisper-large-v3). Frontend is a stub: the
encoder consumes precomputed frame embeddings (B, T_enc, d_model).
Sinusoidal absolute positions (parameter-free; DESIGN.md §5 deviation)."""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import GLOBAL_ATTN, ModelConfig
from repro.models import attention as A
from repro.models import layers as L


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_enc_layer(key, cfg: ModelConfig) -> dict:
    pd = L.pdtype_of(cfg)
    ks = jax.random.split(key, 2)
    return {
        "norm1": jnp.zeros((cfg.d_model,), pd),
        "mixer": A.init_attention(ks[0], cfg),
        "norm2": jnp.zeros((cfg.d_model,), pd),
        "mlp": L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, pd),
    }


def _init_dec_layer(key, cfg: ModelConfig) -> dict:
    pd = L.pdtype_of(cfg)
    ks = jax.random.split(key, 3)
    p = _init_enc_layer(ks[0], cfg)
    p["norm_x"] = jnp.zeros((cfg.d_model,), pd)
    p["cross"] = A.init_cross_attention(ks[1], cfg)
    return p


def init_model(key, cfg: ModelConfig) -> dict:
    pd = L.pdtype_of(cfg)
    k_emb, k_enc, k_dec = jax.random.split(key, 3)
    enc_keys = jax.random.split(k_enc, cfg.encoder_layers)
    dec_keys = jax.random.split(k_dec, cfg.num_layers)
    params: Dict[str, Any] = {
        "embed": L.embed_init(k_emb, cfg.vocab_size, cfg.d_model, pd),
        "enc_layers": jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[_init_enc_layer(k, cfg) for k in enc_keys]),
        "enc_norm": jnp.zeros((cfg.d_model,), pd),
        "dec_layers": jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[_init_dec_layer(k, cfg) for k in dec_keys]),
        "final_norm": jnp.zeros((cfg.d_model,), pd),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(jax.random.fold_in(key, 3),
                                         cfg.d_model, cfg.vocab_size, pd)
    return params


# ---------------------------------------------------------------------------
# encoder
# ---------------------------------------------------------------------------
def encode(params: dict, cfg: ModelConfig, frames: jnp.ndarray) -> jnp.ndarray:
    """frames: (B, T_enc, d_model) stub embeddings -> encoder output."""
    B, T, _ = frames.shape
    x = frames.astype(L.dtype_of(cfg))
    x = x + L.sinusoidal_positions(T, cfg.d_model).astype(x.dtype)[None]
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

    def body(xc, lp):
        from repro.distributed.sharding import constrain_acts
        xc = constrain_acts(xc)     # in-scan batch anchor (DESIGN.md §3)
        h = L.rms_norm(xc, lp["norm1"], cfg.norm_eps)
        mix, _ = A.attention_layer(lp["mixer"], h, pos, cfg, GLOBAL_ATTN,
                                   causal=False)
        xc = xc + mix
        h2 = L.rms_norm(xc, lp["norm2"], cfg.norm_eps)
        return xc + L.apply_mlp(lp["mlp"], h2, cfg.mlp_act), None

    body = jax.checkpoint(body) if cfg.remat != "none" else body
    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return L.rms_norm(x, params["enc_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# decoder
# ---------------------------------------------------------------------------
def _dec_layer(lp, x, cfg, positions, enc_kv, cache, offsets, causal=True):
    from repro.distributed.sharding import constrain_acts
    x = constrain_acts(x)           # in-scan batch anchor (DESIGN.md §3)
    h = L.rms_norm(x, lp["norm1"], cfg.norm_eps)
    mix, new_cache = A.attention_layer(lp["mixer"], h, positions, cfg,
                                       GLOBAL_ATTN, cache, offsets)
    x = x + mix
    hx = L.rms_norm(x, lp["norm_x"], cfg.norm_eps)
    x = x + A.cross_attention_layer(lp["cross"], hx, enc_kv, cfg)
    h2 = L.rms_norm(x, lp["norm2"], cfg.norm_eps)
    return x + L.apply_mlp(lp["mlp"], h2, cfg.mlp_act), new_cache


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               enc_len: Optional[int] = None) -> dict:
    """Self-attn KV per decoder layer + precomputed cross K/V slots."""
    enc_len = enc_len or cfg.num_audio_frames
    dt = L.dtype_of(cfg)
    one = A.init_kv_cache(cfg, GLOBAL_ATTN, batch, max_len)
    nl = cfg.num_layers
    return {
        "self": jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (nl,) + x.shape).copy(), one),
        "xk": jnp.zeros((nl, batch, cfg.num_heads, enc_len, cfg.head_dim), dt),
        "xv": jnp.zeros((nl, batch, cfg.num_heads, enc_len, cfg.head_dim), dt),
    }


def prepare_cross(params: dict, cfg: ModelConfig, enc_out: jnp.ndarray):
    """Precompute per-layer cross K/V from encoder output (prefill time)."""
    def body(_, lp):
        k, v = A.encode_cross_kv(lp["cross"], enc_out, cfg)
        return None, (k, v)
    _, (xk, xv) = jax.lax.scan(body, None, params["dec_layers"])
    return xk, xv


def decode(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
           positions: jnp.ndarray, *,
           enc_out: Optional[jnp.ndarray] = None,
           cache: Optional[dict] = None,
           lengths: Optional[jnp.ndarray] = None,
           ) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[dict]]:
    """Decoder forward.  Train: enc_out given, cache None.  Serve: cache
    holds self KV + precomputed cross KV."""
    B, S = tokens.shape
    x = L.embed_lookup(params["embed"], tokens, cfg)
    # additive sinusoidal positions gathered at absolute offsets
    pos2d = positions if positions.ndim == 2 else positions[0]
    pe_table = L.sinusoidal_positions(1 << 16, cfg.d_model)
    x = x + jnp.take(pe_table, jnp.clip(pos2d, 0, (1 << 16) - 1),
                     axis=0).astype(x.dtype)

    if cache is None:
        xk, xv = prepare_cross(params, cfg, enc_out)
        def body(xa, xs):
            lp, k, v = xs
            xc, _ = _dec_layer(lp, xa, cfg, pos2d, (k, v), None, None)
            return xc, None
        body = jax.checkpoint(body) if cfg.remat != "none" else body
        x, _ = jax.lax.scan(body, x, (params["dec_layers"], xk, xv))
        new_cache = None
    else:
        def body(xa, xs):
            lp, c, k, v = xs
            xc, nc = _dec_layer(lp, xa, cfg, pos2d, (k, v), c, lengths)
            return xc, nc
        x, new_self = jax.lax.scan(
            body, x, (params["dec_layers"], cache["self"],
                      cache["xk"], cache["xv"]))
        new_cache = {"self": new_self, "xk": cache["xk"], "xv": cache["xv"]}

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = L.lm_logits(x, params["embed"], params.get("lm_head"), cfg)
    return logits, jnp.zeros((), jnp.float32), new_cache
