"""Sharding-rule invariants across all archs x modes (+ cache placement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, list_archs, smoke_config, SHAPES
from repro.distributed import sharding as SH
from repro.models.registry import build_model

ARCHS = list_archs()


def _axis_sizes(mesh):
    return dict(mesh.shape)


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("mode", ["train", "serve"])
def test_param_specs_valid_for_full_configs(arch, mode, host_mesh):
    """Full-size configs: every spec uses each mesh axis at most once and
    only on divisible dims — so NamedSharding construction never fails."""
    cfg = get_config(arch)
    m = build_model(cfg)
    sds = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    specs = SH.param_pspecs(cfg, sds, host_mesh, mode)
    sizes = _axis_sizes(host_mesh)
    flat_sds = jax.tree.leaves(sds)
    flat_specs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_sds) == len(flat_specs)
    n_sharded = 0
    for x, spec in zip(flat_sds, flat_specs):
        seen = set()
        for dim, entry in zip(x.shape, tuple(spec)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                assert a not in seen, (arch, spec)
                seen.add(a)
                assert dim % sizes[a] == 0, (arch, x.shape, spec)
            n_sharded += 1
    assert n_sharded > 0, "no parameter sharded at all"


@pytest.mark.parametrize("arch", ["qwen3-8b", "llama4-maverick-400b-a17b",
                                  "deepseek-v2-lite-16b"])
def test_train_mode_shards_ffn_and_experts(arch, host_mesh):
    cfg = get_config(arch)
    m = build_model(cfg)
    sds = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    specs = SH.param_pspecs(cfg, sds, host_mesh, "train")
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    by_name = {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path): s for path, s in flat}
    if cfg.moe is not None:
        e_specs = [s for n, s in by_name.items()
                   if "moe/w_gate" in n or "moe/w_down" in n]
        assert e_specs and all("model" in tuple(s) for s in e_specs), \
            "experts must shard over the model axis (EP)"
    else:
        ffn = [s for n, s in by_name.items() if "mlp/w_gate" in n]
        assert ffn and all("model" in tuple(s) for s in ffn)


def test_serve_mode_drops_fsdp_unless_opted_in(host_mesh):
    cfg = get_config("qwen3-8b")
    m = build_model(cfg)
    sds = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    specs = SH.param_pspecs(cfg, sds, host_mesh, "serve")
    for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
        assert "data" not in tuple(s), s
    # llama4 opts in: weights stay data-sharded in serving
    cfg4 = get_config("llama4-maverick-400b-a17b")
    m4 = build_model(cfg4)
    sds4 = jax.eval_shape(m4.init, jax.random.PRNGKey(0))
    specs4 = SH.param_pspecs(cfg4, sds4, host_mesh, "serve")
    assert any("data" in tuple(s) for s in
               jax.tree.leaves(specs4, is_leaf=lambda x: isinstance(x, P)))


def test_batch_axes_fallback(host_mesh, pod_mesh):
    assert SH.batch_axes(host_mesh, 8) == "data"
    assert SH.batch_axes(host_mesh, 7) is None       # indivisible
    assert SH.batch_axes(pod_mesh, 8) == ("pod", "data")
    assert SH.batch_axes(pod_mesh, 2) == "data"      # falls back


def test_cache_pspecs_head_or_length_over_model(host_mesh):
    """k/v are stored head-major, (..., B, H_kv, T, D).  kv=8 over model=4
    -> heads shard; kv=2 over model=4 -> length shards instead (the
    qwen3-on-16-way case, scaled down); ``shard_length`` puts the length
    on data and keeps the heads on model.  The positions follow the
    length: whole on each model shard when the heads are split there."""
    cfg = get_config("qwen3-8b")          # kv 8 % 4 == 0 on host mesh
    m = build_model(cfg)
    sds = jax.eval_shape(lambda: m.init_cache(8, 64))
    specs = SH.cache_pspecs(cfg, sds, host_mesh)
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    kv = [tuple(s) for path, s in flat
          if str(path[-1].key) in ("k", "v")]
    assert kv and all(t[-3] == "model" for t in kv)       # heads
    assert all(t[-4] == "data" for t in kv)               # slots
    assert all(t[-2] is None for t in kv)                 # length whole
    pos = [tuple(s) for path, s in flat if str(path[-1].key) == "pos"]
    assert pos and all("model" not in t for t in pos)     # whole per chip

    specs_len = SH.cache_pspecs(cfg, sds, host_mesh, shard_length=True)
    for path, s in jax.tree_util.tree_flatten_with_path(
            specs_len, is_leaf=lambda x: isinstance(x, P))[0]:
        if str(path[-1].key) in ("k", "v"):
            t = tuple(s)
            assert t[-2] == "data" and t[-3] == "model", s

    import dataclasses
    cfg2 = dataclasses.replace(cfg, num_kv_heads=2)   # 2 % 4 != 0
    m2 = build_model(cfg2)
    sds2 = jax.eval_shape(lambda: m2.init_cache(8, 64))
    specs2 = SH.cache_pspecs(cfg2, sds2, host_mesh)
    flat2 = jax.tree_util.tree_flatten_with_path(
        specs2, is_leaf=lambda x: isinstance(x, P))[0]
    for path, s in flat2:
        if str(path[-1].key) in ("k", "v"):
            t = tuple(s)
            assert "model" in t, s
            # the length dim (index ndim-2) carries it, not the head dim
            assert t[-2] == "model" and t[-3] is None
        if str(path[-1].key) == "pos":                 # and the positions'
            assert tuple(s)[-1] == "model", s


def test_cache_pspecs_long_context_shards_length_over_data(host_mesh):
    cfg = get_config("gemma2-27b")
    m = build_model(cfg)
    sds = jax.eval_shape(lambda: m.init_cache(1, 4096 * 4))
    specs = SH.cache_pspecs(cfg, sds, host_mesh, shard_length=True)
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    global_kv = [s for path, s in flat
                 if str(path[-1].key) in ("k", "v")
                 and None is not s]
    assert any("data" in tuple(s) for s in global_kv)


def test_constrain_drops_indivisible(host_mesh):
    x = jnp.zeros((6, 5))
    y = SH.constrain(x, host_mesh, "data", "model")   # 6%2==0, 5%4!=0
    assert y.sharding.spec == P("data", None)
