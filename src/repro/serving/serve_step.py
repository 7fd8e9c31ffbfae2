"""Jitted serving steps: batched chunked-prefill and decode.

``build_serve_fns(cfg, mesh, batch, max_len, ...)`` returns the data-plane
programs the engine (and the dry-run) calls:

  * ``prefill_chunk(params, cache, tokens(B,C), lengths(B,), valid_n(B,))``
      -> (next_token (B,), last_logits (B,V), cache)
    Ragged tails are exact: pad entries are written with position -1 and
    recurrent state is untouched past valid_n (see models' ``valid`` path).
  * ``decode(params, cache, tokens(B,), lengths(B,), active(B,))``
      -> (next_token (B,), logits (B,V), cache)
  * ``reset_slots(cache, keep_mask(B,))`` — zero/invalidate freed slots'
    cache rows so re-assigned slots never attend to a previous tenant's KV
    (the paper's memory-isolation requirement R3 at the cache level).

Every function is jitted with donated cache and explicit shardings when a
mesh is supplied; ``decode`` is exactly what launch/dryrun.py lowers for
the decode_32k / long_500k cells.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed import sharding as SH
from repro.models.registry import Model, build_model
from repro.serving.sampler import sample


@dataclasses.dataclass
class ServeFns:
    cfg: ModelConfig
    model: Model
    init_params: Callable[[jax.Array], Any]
    init_cache: Callable[[], Any]
    prefill_chunk: Callable[..., Tuple[jnp.ndarray, jnp.ndarray, Any]]
    decode: Callable[..., Tuple[jnp.ndarray, jnp.ndarray, Any]]
    reset_slots: Callable[[Any, jnp.ndarray], Any]
    param_shardings: Any = None
    cache_shardings: Any = None


def _cache_batch_dim(path_s: str, ndim: int) -> int:
    """Locate the slot/batch dim of a cache leaf by its key name."""
    last = path_s.rsplit("/", 1)[-1]
    if last == "pos" or last == "h":
        return ndim - 2
    if last in ("ckv", "krope") or last.startswith("conv"):
        return ndim - 3
    if last == "state":
        return ndim - 4
    return ndim - 4          # k / v / xk / xv


def _path_str(path) -> str:
    parts = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            parts.append(str(k.key))
        elif isinstance(k, jax.tree_util.SequenceKey):
            parts.append(str(k.idx))
        else:  # pragma: no cover
            parts.append(str(k))
    return "/".join(parts)


def make_reset_slots(cfg: ModelConfig):
    """reset(cache, keep (B,) bool) -> cache with dropped slots invalidated."""

    def reset(cache, keep):
        def leaf(path, x):
            p = _path_str(path)
            bdim = max(_cache_batch_dim(p, x.ndim), 0)
            shape = [1] * x.ndim
            shape[bdim] = x.shape[bdim]
            k = keep.reshape(shape)
            if p.rsplit("/", 1)[-1] == "pos":
                return jnp.where(k, x, -1)
            last = p.rsplit("/", 1)[-1]
            if last in ("h", "state") or last.startswith("conv"):
                return jnp.where(k, x, 0)
            return x          # k/v/ckv payloads are masked by pos
        return jax.tree_util.tree_map_with_path(leaf, cache)

    return reset


def build_serve_fns(cfg: ModelConfig, mesh: Optional[Mesh] = None, *,
                    batch: int, max_len: int, prefill_chunk: int = 256,
                    moe_impl: str = "gshard", temperature: float = 0.0,
                    donate: bool = True, shard_cache_length: bool = False
                    ) -> ServeFns:
    model = build_model(cfg, moe_impl=moe_impl)
    if cfg.window_size:
        prefill_chunk = min(prefill_chunk, cfg.window_size)
    SH.set_activation_mesh(mesh)   # in-scan activation anchors

    # ---- shardings ---------------------------------------------------------
    param_sh = cache_sh = tok_sh = scalar_sh = None
    if mesh is not None:
        params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        pspecs = SH.param_pspecs(cfg, params_sds, mesh, "serve")
        param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                                is_leaf=lambda x: isinstance(x, P))
        cache_sds = jax.eval_shape(
            functools.partial(model.init_cache, batch, max_len))
        cspecs = SH.cache_pspecs(cfg, cache_sds, mesh,
                                 shard_length=shard_cache_length)
        cache_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs,
                                is_leaf=lambda x: isinstance(x, P))
        bspec = SH.batch_pspec(mesh, batch)
        tok_sh = NamedSharding(mesh, bspec)
        scalar_sh = NamedSharding(mesh, bspec)

    # ---- step bodies ---------------------------------------------------------
    def _prefill(params, cache, tokens, lengths, valid_n):
        B, C = tokens.shape
        valid = jnp.arange(C)[None, :] < valid_n[:, None]
        logits, cache = model.prefill(params, tokens, cache, lengths,
                                      valid=valid)
        last = jnp.take_along_axis(
            logits, jnp.maximum(valid_n - 1, 0)[:, None, None], axis=1
        )[:, 0]                                           # (B, V)
        nxt = sample(last, temperature=temperature)
        return nxt, last, cache

    def _decode(params, cache, tokens, lengths, active):
        logits, cache = model.decode_step(
            params, tokens[:, None], cache, lengths,
            valid=active[:, None])
        last = logits[:, -1]                              # (B, V)
        nxt = sample(last, temperature=temperature)
        return nxt, last, cache

    reset = make_reset_slots(cfg)

    # ---- jit ----------------------------------------------------------------
    if mesh is not None:
        prefill_fn = jax.jit(
            _prefill,
            in_shardings=(param_sh, cache_sh, tok_sh, scalar_sh, scalar_sh),
            out_shardings=(scalar_sh, None, cache_sh),
            donate_argnums=(1,) if donate else ())
        decode_fn = jax.jit(
            _decode,
            in_shardings=(param_sh, cache_sh, scalar_sh, scalar_sh,
                          scalar_sh),
            out_shardings=(scalar_sh, None, cache_sh),
            donate_argnums=(1,) if donate else ())
        reset_fn = jax.jit(reset, in_shardings=(cache_sh, scalar_sh),
                           out_shardings=cache_sh,
                           donate_argnums=(0,) if donate else ())
        init_params = jax.jit(model.init, out_shardings=param_sh)
        init_cache = jax.jit(
            functools.partial(model.init_cache, batch, max_len),
            out_shardings=cache_sh)
    else:
        prefill_fn = jax.jit(_prefill, donate_argnums=(1,) if donate else ())
        decode_fn = jax.jit(_decode, donate_argnums=(1,) if donate else ())
        reset_fn = jax.jit(reset, donate_argnums=(0,) if donate else ())
        init_params = jax.jit(model.init)
        init_cache = jax.jit(functools.partial(model.init_cache, batch,
                                               max_len))

    return ServeFns(cfg=cfg, model=model, init_params=init_params,
                    init_cache=init_cache, prefill_chunk=prefill_fn,
                    decode=decode_fn, reset_slots=reset_fn,
                    param_shardings=param_sh, cache_shardings=cache_sh)
