"""Compiles for a described TPU v5e chip (no chip attached).

Each test lowers a program of the main device path against
``ShapeDtypeStruct`` stand-ins placed on a device of a described
``v5e:2x2`` topology and compiles it with the TPU compiler: what the chip's
compiler refuses (unaligned Pallas slices, too much fast memory, 64-bit
types, programs that do not fit HBM) fails here.  Nothing runs, so these
say nothing about results or times.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library at once, and every test worker
imports every test file.  Code that asks ``jax.default_backend()`` still
sees the CPU here, so the tests steer that check with ``monkeypatch``.
"""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu_backend(monkeypatch):
    """Make the repo's backend checks take their TPU branch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _place(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


# ---------------------------------------------------------------------------
# sweep surface
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("T", [8, 128])
def test_rounds_pallas_compiles(one_chip, T):
    from repro.kernels.wlbvt_select import _rounds_pallas
    R = 256
    f32 = jax.ShapeDtypeStruct((R, T), jnp.float32, sharding=one_chip)
    i32 = jax.ShapeDtypeStruct((R, T), jnp.int32, sharding=one_chip)
    fk = jax.ShapeDtypeStruct((R,), jnp.int32, sharding=one_chip)
    for max_picks in (1, 32):
        fn = jax.jit(functools.partial(_rounds_pallas, num_pus=32,
                                       max_picks=max_picks))
        text = fn.lower(f32, i32, i32, f32, f32, fk).compile().as_text()
        assert "tpu_custom_call" in text, max_picks


def _fig9_batch(R, precision, impl):
    from repro.api import get_scenario
    from repro.sim import devicepath as DP
    base = get_scenario("fig9_congestor_victim",
                        duration_us=30.0).replace(record_timeline=False)
    specs = [dataclasses.replace(base, seed=s) for s in range(R)]
    with DP._precision(precision) as ftype:
        geom, state, data, _ = DP._prepare_batch(specs, ftype, "wlbvt", impl)
    # a fresh jit (not the lru-cached one): no trace from a CPU run reused
    return DP._build_launch.__wrapped__(*geom), state, data


def test_fig9_launch_compiles_with_pallas(one_chip, tpu_backend):
    """The fast sweep launch, auto impl: the kernel is compiled in."""
    launch, state, data = _fig9_batch(256, "fast", "")
    compiled = launch.lower(_place(state, one_chip),
                            _place(data, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fig9_exact_launch_compiles_without_kernel(one_chip, tpu_backend):
    """f64 lanes, auto impl: the jnp select, through XLA's f64 emulation."""
    with jax.enable_x64(True):
        launch, state, data = _fig9_batch(8, "exact", "")
        compiled = launch.lower(_place(state, one_chip),
                                _place(data, one_chip)).compile()
    assert "tpu_custom_call" not in compiled.as_text()


def test_f64_pallas_raises(tpu_backend):
    from repro.kernels.wlbvt_select import wlbvt_select_rounds
    R, T = 8, 4
    with jax.enable_x64(True):
        f64 = np.ones((R, T), np.float64)
        i32 = np.ones((R, T), np.int32)
        with pytest.raises(ValueError, match="f32 lanes only"):
            wlbvt_select_rounds(f64, i32, i32, f64, f64,
                                np.ones(R, np.int32), num_pus=32,
                                max_picks=1, impl="pallas")


# ---------------------------------------------------------------------------
# serving surface
# ---------------------------------------------------------------------------
def test_qwen3_decode_step_compiles(one_chip):
    """One decode step of Qwen3-8B at published widths, 2 layers, bf16,
    with the serving geometry of the benchmark's serve cells (8 slots x
    2048 tokens, 256-token prefill chunks)."""
    from repro.configs import get_config
    from repro.serving.serve_step import build_serve_fns
    cfg = dataclasses.replace(get_config("qwen3-8b"), num_layers=2,
                              param_dtype="bfloat16")
    B, L = 8, 2048
    fns = build_serve_fns(cfg, None, batch=B, max_len=L, prefill_chunk=256)
    params = jax.eval_shape(fns.model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(functools.partial(fns.model.init_cache, B, L))
    i32 = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    act = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip)
    compiled = fns.decode.lower(_place(params, one_chip),
                                _place(cache, one_chip),
                                i32, i32, act).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES
    out = jax.eval_shape(fns.decode, params, cache, i32, i32, act)
    assert out[1].shape == (B, cfg.vocab_size)


@pytest.mark.parametrize("chips", [1, 4])
def test_qwen3_prefill_rows_compiles(topo, chips):
    """The serving prefill program two rows wide (``prefill_rows``) at
    Qwen3-8B's published widths, 2 layers, bf16, 8 slots x 2048, on one
    chip and tensor parallel on ``(data=1, model=4)``: its layer loop and
    its all-reduces carry the 2 rows alone, and the cache is updated in
    place."""
    import re
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.serving.serve_step import build_serve_fns
    cfg = dataclasses.replace(get_config("qwen3-8b"), num_layers=2,
                              param_dtype="bfloat16", tie_embeddings=False)
    B, L, C = 8, 2048, 256
    mesh = (make_mesh((1, 4), ("data", "model"), devices=topo.devices)
            if chips == 4 else None)
    fns = build_serve_fns(cfg, mesh, batch=B, max_len=L, prefill_chunk=C,
                          prefill_width=2)
    params = jax.eval_shape(fns.model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(functools.partial(fns.model.init_cache, B, L))
    if mesh is None:
        one = SingleDeviceSharding(topo.devices[0])
        params, cache = _place(params, one), _place(cache, one)
        row = one
    else:
        params, cache = (
            jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=s), t, sh)
            for t, sh in ((params, fns.param_shardings),
                          (cache, fns.cache_shardings)))
        row = NamedSharding(mesh, PartitionSpec())
    toks = jax.ShapeDtypeStruct((2, C), jnp.int32, sharding=row)
    i32 = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=row)
    compiled = fns.prefill_rows.lower(params, cache, toks, i32, i32,
                                      i32).compile()
    text = compiled.as_text()
    assert "bf16[2,256,4096]" in text and "bf16[8,256,4096]" not in text
    if chips == 4:
        assert set(re.findall(r"(\w+\[[\d,]+\])\S* all-reduce\(", text)) \
            == {"bf16[2,256,4096]"}
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(cache)) // chips
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes


def _computations(text):
    """{name: (is_entry, [instruction lines])} of an HLO module's text,
    without the bodies of fusions (what runs inside a fusion is never a
    buffer of its own)."""
    import re
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"(ENTRY )?%(\S+) .*\{\s*$", line)
        if m and not line.startswith(" "):
            cur = m.group(2)
            comps[cur] = (bool(m.group(1)), [])
        elif cur and line.strip().startswith(("%", "ROOT %")):
            comps[cur][1].append(line)
    fused = {c for _, lines in comps.values() for line in lines
             if " fusion(" in line
             for c in re.findall(r"calls=%([\w.\-]+)", line)}
    return {c: v for c, v in comps.items() if c not in fused}


def _buffers(text):
    """(in_loop, name, opcode, shape, bytes) of every array an HLO module
    materializes outside fusion bodies; ``in_loop``: not in the entry
    computation (a while body, here the layer and attention loops)."""
    import re
    width = {"bf16": 2, "f32": 4, "s32": 4, "pred": 1}
    out = []
    for entry, lines in _computations(text).values():
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* "
                         r"([\w-]+)\(", line)
            if not m or m.group(2) not in width:
                continue
            shape = tuple(int(d) for d in m.group(3).split(",") if d)
            target = re.search(r'custom_call_target="(\w+)"', line)
            op = m.group(4) + (":" + target.group(1) if target else "")
            out.append((not entry, m.group(1), op, shape,
                        width[m.group(2)] * int(np.prod(shape))))
    return out


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("program", ["decode", "prefill_rows"])
def test_serving_programs_write_the_kv_cache_in_place(topo, program, chips):
    """Qwen3-8B at published widths, 4 layers, bf16, 8 slots x 2048, on
    one chip and tensor parallel on ``(data=1, model=4)``.  The layer scan
    carries the stacked KV cache: the only arrays of a layer's K/V size in
    the layer loops are the in-place ``dynamic-update-slice``s of the
    carried buffers (no per-layer slice, relayout or scan output), no copy
    of the whole cache is made, and the exchange carries activations
    only.  Decode's temporaries stay under one layer's K+V.  (Prefill's
    hold by design the chunk's f32 logits and the rows it gathers, so no
    such bound fits it.)"""
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.serving.serve_step import build_serve_fns
    cfg = dataclasses.replace(get_config("qwen3-8b"), num_layers=4,
                              param_dtype="bfloat16", tie_embeddings=False)
    B, L, C, P = 8, 2048, 256, 2
    mesh = (make_mesh((1, 4), ("data", "model"), devices=topo.devices)
            if chips == 4 else None)
    fns = build_serve_fns(cfg, mesh, batch=B, max_len=L, prefill_chunk=C,
                          prefill_width=P)
    params = jax.eval_shape(fns.model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(functools.partial(fns.model.init_cache, B, L))
    if mesh is None:
        one = SingleDeviceSharding(topo.devices[0])
        params, cache = _place(params, one), _place(cache, one)
        row = one
    else:
        params, cache = (
            jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=s), t, sh)
            for t, sh in ((params, fns.param_shardings),
                          (cache, fns.cache_shardings)))
        row = NamedSharding(mesh, PartitionSpec())
    if program == "decode":
        i32 = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=row)
        act = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=row)
        compiled = fns.decode.lower(params, cache, i32, i32, act).compile()
        rows, acts = B, {f"bf16[{B},1,{cfg.d_model}]"}
    else:
        toks = jax.ShapeDtypeStruct((P, C), jnp.int32, sharding=row)
        i32 = jax.ShapeDtypeStruct((P,), jnp.int32, sharding=row)
        compiled = fns.prefill_rows.lower(params, cache, toks, i32, i32,
                                          i32).compile()
        rows, acts = P, {f"bf16[{P},{C},{cfg.d_model}]"}
    text = compiled.as_text()
    kv = cache["groups"][0]["k"]                     # (layers, B, H, L, D)
    assert kv.shape == (4, B, cfg.num_kv_heads, L, cfg.head_dim)
    heads = cfg.num_kv_heads // chips
    leaf = B * heads * L * cfg.head_dim * 2          # one layer's K, a chip
    stacked = (4, B, heads, L, cfg.head_dim)
    # (the async moves between memory spaces that the compiler adds at this
    # depth, copy-/slice-done and the slices' ConcatBitcast, keep the
    # layout; at the cells' 16 and 36 layers it adds none)
    in_place = {"dynamic-update-slice", "get-tuple-element", "parameter",
                "bitcast", "tuple", "copy-done", "slice-done",
                "custom-call:ConcatBitcast"}
    for in_loop, name, op, shape, nbytes in _buffers(text):
        if in_loop and L in shape and nbytes >= leaf * rows // B:
            assert op in in_place, (name, op, shape)
        if op == "copy":
            assert shape != stacked, (name, shape)
    if chips == 4:
        import re
        assert set(re.findall(r"(\w+\[[\d,]+\])\S* all-reduce\(", text)) \
            == acts
        for size in re.findall(r"\w+\[([\d,]+)\]\S* all-gather\(", text):
            assert np.prod([int(d) for d in size.split(",")]) <= 64, size
    mem = compiled.memory_analysis()
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(cache)) // chips
    assert mem.alias_size_in_bytes >= cache_bytes
    if program == "decode":
        assert mem.temp_size_in_bytes < 2 * leaf
