"""Decoder-only LM assembly: block dispatch + scan-over-layers + caches.

Layers are grouped into a repeating *group* of length
``lcm(len(block_pattern), moe_period)``; full groups are stacked and scanned
(one compiled body regardless of depth), leading ``first_dense_layers`` and
any trailing partial group are applied unscanned.  Each layer kind
(attention global/local, SSD, RG-LRU) carries its own cache pytree.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (GLOBAL_ATTN, LOCAL_ATTN, RGLRU, SSD,
                                ModelConfig)
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import ssm as S


# ---------------------------------------------------------------------------
# layer layout
# ---------------------------------------------------------------------------
def _lcm(a: int, b: int) -> int:
    return a * b // math.gcd(a, b)


def layer_layout(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    """(front, group_len, n_groups, tail) layer partition."""
    front = cfg.moe.first_dense_layers if cfg.moe else 0
    p = len(cfg.block_pattern)
    if cfg.moe:
        p = _lcm(p, cfg.moe.layer_period)
    rest = cfg.num_layers - front
    n_groups = rest // p if cfg.scan_layers else 0
    tail = rest - n_groups * p
    return front, p, n_groups, tail


def _layer_sig(cfg: ModelConfig, i: int) -> Tuple[str, bool]:
    pattern = cfg.pattern_for_layers()
    moe_mask = cfg.moe_layer_mask()
    return pattern[i], moe_mask[i]


# ---------------------------------------------------------------------------
# single-layer init / apply
# ---------------------------------------------------------------------------
def init_layer(key, cfg: ModelConfig, kind: str, is_moe: bool) -> dict:
    pd = L.pdtype_of(cfg)
    ks = jax.random.split(key, 3)
    p: Dict[str, Any] = {"norm1": jnp.zeros((cfg.d_model,), pd)}
    if kind in (GLOBAL_ATTN, LOCAL_ATTN):
        p["mixer"] = A.init_attention(ks[0], cfg)
    elif kind == SSD:
        p["mixer"] = S.init_ssd(ks[0], cfg)
        if cfg.use_post_norms:
            p["post_norm1"] = jnp.zeros((cfg.d_model,), pd)
        return p  # SSD block has no separate MLP
    elif kind == RGLRU:
        p["mixer"] = R.init_rglru(ks[0], cfg)
    else:
        raise ValueError(kind)
    p["norm2"] = jnp.zeros((cfg.d_model,), pd)
    if is_moe:
        p["moe"] = M.init_moe(ks[1], cfg)
    else:
        p["mlp"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, pd)
    if cfg.use_post_norms:
        p["post_norm1"] = jnp.zeros((cfg.d_model,), pd)
        p["post_norm2"] = jnp.zeros((cfg.d_model,), pd)
    return p


def _recurrent(block, cache, layer, *args):
    """A recurrent block on its layer's entry of a stacked ``cache``
    (``layer`` not None): its small state is replaced whole."""
    if layer is None:
        return block(cache, *args)
    out, new = block(jax.tree.map(
        lambda c: jax.lax.dynamic_index_in_dim(c, layer, 0, False), cache),
        *args)
    return out, jax.tree.map(
        lambda c, n: jax.lax.dynamic_update_index_in_dim(
            c, n.astype(c.dtype), layer, 0), cache, new)


def apply_layer(lp: dict, x: jnp.ndarray, cfg: ModelConfig, kind: str,
                is_moe: bool, positions, seg, cache, offsets,
                moe_impl: str, valid=None, layer=None
                ) -> Tuple[jnp.ndarray, Any, jnp.ndarray]:
    """``layer``: ``cache`` is a layer stack and this layer its entry
    ``layer`` (the serving scan carries the stack and writes in place)."""
    from repro.distributed.sharding import constrain_acts
    x = constrain_acts(x)      # re-anchor batch sharding inside scan bodies
    h = L.rms_norm(x, lp["norm1"], cfg.norm_eps)
    if kind in (GLOBAL_ATTN, LOCAL_ATTN):
        mix, new_cache = A.attention_layer(lp["mixer"], h, positions, cfg,
                                           kind, cache, offsets, seg,
                                           layer=layer)
    elif kind == SSD:
        mix, new_cache = _recurrent(
            lambda c: S.ssd_block(lp["mixer"], h, cfg, c, valid),
            cache, layer)
    elif kind == RGLRU:
        mix, new_cache = _recurrent(
            lambda c: R.rglru_block(lp["mixer"], h, cfg, c, valid),
            cache, layer)
    else:
        raise ValueError(kind)
    if cfg.use_post_norms:
        mix = L.rms_norm(mix, lp["post_norm1"], cfg.norm_eps)
    x = x + mix
    aux = jnp.zeros((), jnp.float32)
    if kind == SSD:
        return x, new_cache, aux
    h2 = L.rms_norm(x, lp["norm2"], cfg.norm_eps)
    if is_moe:
        y, aux = M.apply_moe(lp["moe"], h2, cfg, moe_impl)
    else:
        y = L.apply_mlp(lp["mlp"], h2, cfg.mlp_act)
    if cfg.use_post_norms:
        y = L.rms_norm(y, lp["post_norm2"], cfg.norm_eps)
    return x + y, new_cache, aux


# ---------------------------------------------------------------------------
# model init
# ---------------------------------------------------------------------------
def init_model(key, cfg: ModelConfig) -> dict:
    pd = L.pdtype_of(cfg)
    front, p, n_groups, tail = layer_layout(cfg)
    keys = jax.random.split(key, cfg.num_layers + 2)
    params: Dict[str, Any] = {
        "embed": L.embed_init(keys[0], cfg.vocab_size, cfg.d_model, pd),
        "final_norm": jnp.zeros((cfg.d_model,), pd),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(keys[1], cfg.d_model,
                                         cfg.vocab_size, pd)

    def make(i):
        kind, is_moe = _layer_sig(cfg, i)
        return init_layer(keys[2 + i], cfg, kind, is_moe)

    params["front"] = [make(i) for i in range(front)]
    groups = []
    for g in range(n_groups):
        groups.append(tuple(make(front + g * p + j) for j in range(p)))
    if groups:
        params["groups"] = jax.tree.map(lambda *xs: jnp.stack(xs), *groups)
    params["tail"] = [make(front + n_groups * p + j) for j in range(tail)]
    return params


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------
def _layer_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int):
    if kind in (GLOBAL_ATTN, LOCAL_ATTN):
        return A.init_kv_cache(cfg, kind, batch, max_len)
    if kind == SSD:
        return S.init_ssd_cache(cfg, batch)
    if kind == RGLRU:
        return R.init_rglru_cache(cfg, batch)
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    front, p, n_groups, tail = layer_layout(cfg)
    cache: Dict[str, Any] = {
        "front": [
            _layer_cache(cfg, _layer_sig(cfg, i)[0], batch, max_len)
            for i in range(front)],
        "tail": [
            _layer_cache(cfg, _layer_sig(cfg, front + n_groups * p + j)[0],
                         batch, max_len)
            for j in range(tail)],
    }
    if n_groups:
        one = tuple(_layer_cache(cfg, _layer_sig(cfg, front + j)[0],
                                 batch, max_len) for j in range(p))
        cache["groups"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_groups,) + x.shape).copy(),
            one)
    return cache


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return jax.checkpoint(fn)  # "full"


def forward(params: dict, cfg: ModelConfig, tokens: Optional[jnp.ndarray],
            positions: jnp.ndarray, *,
            seg: Optional[jnp.ndarray] = None,
            cache: Optional[dict] = None,
            lengths: Optional[jnp.ndarray] = None,
            vis_embeds: Optional[jnp.ndarray] = None,
            vis_mask: Optional[jnp.ndarray] = None,
            moe_impl: str = "gshard",
            inputs_embeds: Optional[jnp.ndarray] = None,
            valid: Optional[jnp.ndarray] = None,
            ) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[dict]]:
    """Returns (logits fp32, moe_aux, new_cache).

    Train/prefill-from-zero: cache=None.  Serving: cache + lengths (B,) =
    current fill; positions must be absolute.  VLM stub: vis_embeds/vis_mask
    splice precomputed patch embeddings into the token stream.
    """
    front, p, n_groups, tail = layer_layout(cfg)
    if inputs_embeds is not None:
        x = inputs_embeds.astype(L.dtype_of(cfg))
        if cfg.scale_embeddings:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    else:
        x = L.embed_lookup(params["embed"], tokens, cfg)
    from repro.distributed.sharding import constrain_acts
    x = constrain_acts(x)
    if vis_embeds is not None:
        x = jnp.where(vis_mask[..., None], vis_embeds.astype(x.dtype), x)

    offsets = lengths if lengths is not None else None
    aux_total = jnp.zeros((), jnp.float32)

    def run(i, lp, xc, c):
        kind, is_moe = _layer_sig(cfg, i)
        return apply_layer(lp, xc, cfg, kind, is_moe, positions, seg, c,
                           offsets, moe_impl, valid)

    new_front = []
    for i, lp in enumerate(params["front"]):
        c = cache["front"][i] if cache is not None else None
        x, nc, aux = run(i, lp, x, c)
        aux_total = aux_total + aux
        new_front.append(nc)

    new_groups = None
    if n_groups:
        sigs = [_layer_sig(cfg, front + j) for j in range(p)]

        def group_fn(xc, aux_c, gp, gc, li):
            new_cs = []
            for j in range(p):
                kind, is_moe = sigs[j]
                c = gc[j] if gc is not None else None
                xc, nc, aux = apply_layer(gp[j], xc, cfg, kind, is_moe,
                                          positions, seg, c, offsets,
                                          moe_impl, valid, layer=li)
                aux_c = aux_c + aux
                new_cs.append(nc)
            return xc, aux_c, tuple(new_cs)

        if cache is None:
            train_fn = _remat(
                lambda xa, gp: group_fn(*xa, gp, None, None)[:2], cfg)
            (x, aux_total), _ = jax.lax.scan(
                lambda xa, gp: (train_fn(xa, gp), None),
                (x, aux_total), params["groups"])
        else:
            # the stacked cache rides in the carry: each layer writes its
            # new entries into it in place and reads its own entry of it
            def step(carry, gp):
                xc, aux_c, gc, li = carry
                xc, aux_c, gc = group_fn(xc, aux_c, gp, gc, li)
                return (xc, aux_c, gc, li + 1), None

            (x, aux_total, new_groups, _), _ = jax.lax.scan(
                step, (x, aux_total, cache["groups"], jnp.int32(0)),
                params["groups"])

    new_tail = []
    for j, lp in enumerate(params["tail"]):
        i = front + n_groups * p + j
        c = cache["tail"][j] if cache is not None else None
        x, nc, aux = run(i, lp, x, c)
        aux_total = aux_total + aux
        new_tail.append(nc)

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = L.lm_logits(x, params["embed"], params.get("lm_head"), cfg)
    new_cache = None
    if cache is not None:
        new_cache = {"front": new_front, "groups": new_groups,
                     "tail": new_tail}
    return logits, aux_total, new_cache


def make_positions(cfg: ModelConfig, batch: int, seq: int,
                   start: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(B,S) positions, or (3,B,S) identical streams for M-RoPE text."""
    base = jnp.arange(seq, dtype=jnp.int32)[None, :]
    pos = jnp.broadcast_to(base, (batch, seq))
    if start is not None:
        pos = pos + start[:, None]
    if cfg.mrope_sections:
        pos = jnp.broadcast_to(pos[None], (3, batch, seq))
    return pos
