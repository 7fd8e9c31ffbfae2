"""Production mesh + ShapeDtypeStruct input specs for the dry-run.

``make_production_mesh`` is a *function* (not a module constant) so
importing this module never touches jax device state — the dry-run driver
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any
jax import and only then builds the mesh.

Target: TPU v5e pods.  Single pod = 16x16 = 256 chips, mesh
(data=16, model=16).  Multi-pod = 2 pods = 512 chips, mesh
(pod=2, data=16, model=16); the ``pod`` axis carries extra DP by default
or pipeline stages (distributed/pipeline.py).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeSpec
from repro.distributed import sharding as SH


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules in
    ``distributed/sharding.py`` are ``with_sharding_constraint`` /
    ``NamedSharding`` hints for the partitioner, which jax rejects on the
    ``Explicit`` axes ``make_mesh`` defaults to."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape: Tuple[int, ...] = (2, 4),
                   axes: Tuple[str, ...] = ("data", "model")) -> Mesh:
    """Small mesh over forced host devices (tests)."""
    return make_mesh(shape, axes)


# ---------------------------------------------------------------------------
# input specs: ShapeDtypeStructs with shardings attached — no allocation
# ---------------------------------------------------------------------------
def _sds(shape, dtype, mesh, spec) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def input_specs(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh
                ) -> Dict[str, jax.ShapeDtypeStruct]:
    """Stand-ins for every model input of the (arch, shape) cell.

    train:   {tokens, labels} (+frames / vis_embeds+vis_mask stubs)
    prefill: {tokens, lengths}
    decode:  {tokens (B,), lengths (B,)} — one new token against a KV cache
             of shape.seq_len (the cache itself comes from cache_specs()).
    """
    B, S = shape.global_batch, shape.seq_len
    bentry = SH.batch_axes(mesh, B)
    bspec = P(bentry)
    bspec2 = P(bentry, None)
    out: Dict[str, jax.ShapeDtypeStruct] = {}
    if shape.kind == "train":
        out["tokens"] = _sds((B, S), jnp.int32, mesh, bspec2)
        out["labels"] = _sds((B, S), jnp.int32, mesh, bspec2)
        if cfg.is_encoder_decoder:
            out["frames"] = _sds((B, cfg.num_audio_frames, cfg.d_model),
                                 jnp.float32, mesh, P(bentry, None, None))
        elif cfg.frontend_stub:
            out["vis_embeds"] = _sds((B, S, cfg.d_model), jnp.bfloat16,
                                     mesh, P(bentry, None, None))
            out["vis_mask"] = _sds((B, S), jnp.bool_, mesh, bspec2)
    elif shape.kind == "prefill":
        out["tokens"] = _sds((B, S), jnp.int32, mesh, bspec2)
        out["lengths"] = _sds((B,), jnp.int32, mesh, bspec)
        if cfg.is_encoder_decoder:
            out["frames"] = _sds((B, cfg.num_audio_frames, cfg.d_model),
                                 jnp.float32, mesh, P(bentry, None, None))
    else:  # decode
        out["tokens"] = _sds((B,), jnp.int32, mesh, bspec)
        out["lengths"] = _sds((B,), jnp.int32, mesh, bspec)
    return out


def cache_specs(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh,
                model=None) -> Dict:
    """ShapeDtypeStructs for the KV cache of a decode cell."""
    import functools
    from repro.models.registry import build_model
    model = model or build_model(cfg)
    B, S = shape.global_batch, shape.seq_len
    sds = jax.eval_shape(functools.partial(model.init_cache, B, S))
    shard_len = (B == 1)      # long_500k: batch=1 -> shard cache length
    specs = SH.cache_pspecs(cfg, sds, mesh, shard_length=shard_len)
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=NamedSharding(mesh, s)),
        sds, specs, is_leaf=lambda x: hasattr(x, "shape"))
