"""Jitted serving steps: batched chunked-prefill and decode.

``build_serve_fns(cfg, mesh, batch, max_len, ...)`` returns the data-plane
programs the engine (and the dry-run) calls:

  * ``prefill_chunk(params, cache, tokens(B,C), lengths(B,), valid_n(B,))``
      -> (next_token (B,), last_logits (B,V), cache)
    Ragged tails are exact: pad entries are not written to the KV cache
    and recurrent state is untouched past valid_n (see models' ``valid``
    path).
    The dry-run lowers it; the engine's executor runs ``prefill_rows``.
  * ``prefill_rows(params, cache, tokens(P,C), lengths(P,), valid_n(P,),
    slots(P,))`` -> (next_token (P,), last_logits (P,V), cache), P =
    ``prefill_width``: the same step over the cache rows ``slots`` only,
    gathered, prefilled and written back inside the one program; a row
    with ``valid_n == 0`` goes back unchanged, and no other slot's row is
    touched.  ``slots`` must be distinct.
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
    prefill_rows: Callable[..., Tuple[jnp.ndarray, jnp.ndarray, Any]]
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


def _batch_leaf(path, x) -> int:
    return max(_cache_batch_dim(_path_str(path), x.ndim), 0)


def _take_rows(cache, slots):
    """The rows ``slots`` (P,) of every cache leaf, along its batch dim."""
    def leaf(path, x):
        b = _batch_leaf(path, x)
        return jnp.concatenate(
            [jax.lax.dynamic_slice_in_dim(x, slots[i], 1, axis=b)
             for i in range(slots.shape[0])], axis=b)
    return jax.tree_util.tree_map_with_path(leaf, cache)


def _put_rows(cache, slots, rows):
    """``cache`` with the rows ``slots`` (P,) of every leaf set to ``rows``
    (one in-place update per row)."""
    def leaf(path, x, r):
        b = _batch_leaf(path, x)
        for i in range(slots.shape[0]):
            x = jax.lax.dynamic_update_slice_in_dim(
                x, jax.lax.slice_in_dim(r, i, i + 1, axis=b), slots[i],
                axis=b)
        return x
    return jax.tree_util.tree_map_with_path(leaf, cache, rows)


def make_reset_slots(cfg: ModelConfig):
    """reset(cache, keep (B,) bool) -> cache with dropped slots invalidated."""

    def reset(cache, keep):
        def leaf(path, x):
            p = _path_str(path)
            bdim = _batch_leaf(path, x)
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
                    prefill_width: Optional[int] = None,
                    moe_impl: str = "gshard", temperature: float = 0.0,
                    donate: bool = True, shard_cache_length: bool = False
                    ) -> ServeFns:
    """``prefill_width``: the rows of ``prefill_rows`` (at most
    ``batch``, which None means)."""
    model = build_model(cfg, moe_impl=moe_impl)
    width = min(prefill_width or batch, batch)
    if cfg.window_size:
        prefill_chunk = min(prefill_chunk, cfg.window_size)
    SH.set_activation_mesh(mesh)   # in-scan activation anchors

    # ---- shardings ---------------------------------------------------------
    param_sh = cache_sh = tok_sh = scalar_sh = row_sh = sub_sh = None
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
        # the gathered rows keep the cache's placement (heads, or the
        # position leaf's length, on ``model``)
        sub_sds = jax.eval_shape(
            functools.partial(model.init_cache, width, max_len))
        sub_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            SH.cache_pspecs(cfg, sub_sds, mesh,
                            shard_length=shard_cache_length),
            is_leaf=lambda x: isinstance(x, P))
        row_sh = NamedSharding(mesh, SH.batch_pspec(mesh, width))

    # ---- step bodies ---------------------------------------------------------
    def _rows(tree):
        if sub_sh is None:
            return tree
        return jax.lax.with_sharding_constraint(tree, sub_sh)

    def _prefill(params, cache, tokens, lengths, valid_n, slots=None):
        """One prefill chunk over every slot (``slots`` None), or over the
        cache rows ``slots`` alone (see the module docstring)."""
        full = cache
        if slots is not None:
            cache = _rows(_take_rows(full, slots))
        valid = jnp.arange(tokens.shape[1])[None, :] < valid_n[:, None]
        logits, new = model.prefill(params, tokens, cache, lengths,
                                    valid=valid)
        last = jnp.take_along_axis(
            logits, jnp.maximum(valid_n - 1, 0)[:, None, None], axis=1
        )[:, 0]                                           # (B, V)
        nxt = sample(last, temperature=temperature)
        if slots is None:
            return nxt, last, new
        # a row with valid_n == 0 comes back as it was read: pad entries
        # are not written, and recurrent state is inert on them
        return nxt, last, _put_rows(full, slots, _rows(new))

    def _decode(params, cache, tokens, lengths, active):
        logits, cache = model.decode_step(
            params, tokens[:, None], cache, lengths,
            valid=active[:, None])
        last = logits[:, -1]                              # (B, V)
        nxt = sample(last, temperature=temperature)
        return nxt, last, cache

    reset = make_reset_slots(cfg)

    # ---- jit ----------------------------------------------------------------
    # both prefill programs jit ``_prefill``, so each is ``jit__prefill`` on
    # a device trace
    if mesh is not None:
        prefill_fn = jax.jit(
            _prefill,
            in_shardings=(param_sh, cache_sh, tok_sh, scalar_sh, scalar_sh),
            out_shardings=(scalar_sh, None, cache_sh),
            donate_argnums=(1,) if donate else ())
        rows_fn = jax.jit(
            _prefill,
            in_shardings=(param_sh, cache_sh) + (row_sh,) * 4,
            out_shardings=(row_sh, None, cache_sh),
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
        rows_fn = jax.jit(_prefill, donate_argnums=(1,) if donate else ())
        decode_fn = jax.jit(_decode, donate_argnums=(1,) if donate else ())
        reset_fn = jax.jit(reset, donate_argnums=(0,) if donate else ())
        init_params = jax.jit(model.init)
        init_cache = jax.jit(functools.partial(model.init_cache, batch,
                                               max_len))

    return ServeFns(cfg=cfg, model=model, init_params=init_params,
                    init_cache=init_cache, prefill_chunk=prefill_fn,
                    decode=decode_fn, reset_slots=reset_fn,
                    param_shardings=param_sh, cache_shardings=cache_sh,
                    prefill_rows=rows_fn)
