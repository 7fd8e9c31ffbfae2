"""Unified model API over decoder-only and encoder-decoder stacks.

``build_model(cfg)`` returns a ``Model`` with:
  init(rng)                                  -> params
  forward(params, batch)                     -> (logits, aux)          # train
  init_cache(batch, max_len)                 -> cache
  prefill(params, tokens/..., cache, lengths)-> (logits, cache)
  decode_step(params, tokens, cache, lengths)-> (logits, cache)

``batch`` is a dict; see ``input_names(cfg, kind)`` for the contract used by
input_specs()/the data pipeline.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, transformer


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[[Any], dict]
    forward: Callable[..., Tuple[jnp.ndarray, jnp.ndarray]]
    init_cache: Callable[..., dict]
    prefill: Callable[..., Tuple[jnp.ndarray, dict]]
    decode_step: Callable[..., Tuple[jnp.ndarray, dict]]


def input_names(cfg: ModelConfig, kind: str) -> Tuple[str, ...]:
    if cfg.is_encoder_decoder:
        if kind == "train":
            return ("frames", "tokens", "labels")
        return ("tokens",)
    if cfg.frontend_stub:  # vlm
        if kind == "train":
            return ("tokens", "vis_embeds", "vis_mask", "labels")
        return ("tokens",)
    if kind == "train":
        return ("tokens", "labels")
    return ("tokens",)


def build_model(cfg: ModelConfig, moe_impl: str = "ragged") -> Model:
    if cfg.is_encoder_decoder:
        return _build_encdec(cfg)
    return _build_decoder_only(cfg, moe_impl)


# ---------------------------------------------------------------------------
def _build_decoder_only(cfg: ModelConfig, moe_impl: str) -> Model:
    def init(rng):
        return transformer.init_model(rng, cfg)

    def forward(params, batch):
        tokens = batch["tokens"]
        B, S = tokens.shape
        positions = batch.get("positions")
        if positions is None:
            positions = transformer.make_positions(cfg, B, S)
        logits, aux, _ = transformer.forward(
            params, cfg, tokens, positions,
            seg=batch.get("segment_ids"),
            vis_embeds=batch.get("vis_embeds"),
            vis_mask=batch.get("vis_mask"),
            moe_impl=moe_impl)
        return logits, aux

    def init_cache(batch, max_len):
        return transformer.init_cache(cfg, batch, max_len)

    def prefill(params, tokens, cache, lengths, valid=None, **kw):
        """``valid`` (B,S) bool: ragged chunk tails / inactive decode slots.
        Pad entries get position -1 and are not written to the KV cache;
        recurrent blocks treat them as exactly inert."""
        B, S = tokens.shape
        positions = transformer.make_positions(cfg, B, S, start=lengths)
        if valid is not None:
            vmask = valid if positions.ndim == 2 else valid[None]
            positions = jnp.where(vmask, positions, -1)
        logits, _, cache = transformer.forward(
            params, cfg, tokens, positions, cache=cache, lengths=lengths,
            vis_embeds=kw.get("vis_embeds"), vis_mask=kw.get("vis_mask"),
            moe_impl=moe_impl, valid=valid)
        return logits, cache

    def decode_step(params, tokens, cache, lengths, valid=None):
        return prefill(params, tokens, cache, lengths, valid=valid)

    return Model(cfg, init, forward, init_cache, prefill, decode_step)


# ---------------------------------------------------------------------------
def _build_encdec(cfg: ModelConfig) -> Model:
    def init(rng):
        return encdec.init_model(rng, cfg)

    def forward(params, batch):
        frames, tokens = batch["frames"], batch["tokens"]
        B, S = tokens.shape
        enc_out = encdec.encode(params, cfg, frames)
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        logits, aux, _ = encdec.decode(params, cfg, tokens, positions,
                                       enc_out=enc_out)
        return logits, aux

    def init_cache(batch, max_len):
        return encdec.init_cache(cfg, batch, max_len)

    def prefill(params, tokens, cache, lengths, frames=None, valid=None,
                **kw):
        """First call may carry encoder frames to fill the cross KV."""
        if frames is not None:
            enc_out = encdec.encode(params, cfg, frames)
            xk, xv = encdec.prepare_cross(params, cfg, enc_out)
            cache = {"self": cache["self"], "xk": xk, "xv": xv}
        B, S = tokens.shape
        positions = (jnp.arange(S, dtype=jnp.int32)[None]
                     + lengths[:, None]).astype(jnp.int32)
        if valid is not None:
            positions = jnp.where(valid, positions, -1)
        logits, _, cache = encdec.decode(params, cfg, tokens, positions,
                                         cache=cache, lengths=lengths)
        return logits, cache

    def decode_step(params, tokens, cache, lengths, valid=None):
        return prefill(params, tokens, cache, lengths, valid=valid)

    return Model(cfg, init, forward, init_cache, prefill, decode_step)
