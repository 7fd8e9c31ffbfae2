"""Collectives overlap, gradient compression, pipeline parallelism."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed import collectives as C
from repro.distributed import compression as Q
from repro.distributed import pipeline as PP


# ---------------------------------------------------------------------------
# collective matmul (all-gather <-> matmul overlap)
# ---------------------------------------------------------------------------
def test_collective_matmul_ag_matches_dense(host_mesh):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    d_in, d_out, B = 32, 24, 8
    x = jax.random.normal(k1, (B, d_in), jnp.float32)
    w = jax.random.normal(k2, (d_in, d_out), jnp.float32)
    n = host_mesh.shape["model"]

    fn = shard_map(
        functools.partial(C.collective_matmul_ag, axis_name="model"),
        mesh=host_mesh,
        in_specs=(P(), P("model", None)),
        out_specs=P(), check_vma=False)
    got = fn(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                               rtol=1e-5, atol=1e-5)


def test_reduce_scatter_matmul_matches_dense(host_mesh):
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    d_in, d_out, B = 32, 24, 8
    x = jax.random.normal(k1, (B, d_in), jnp.float32)
    w = jax.random.normal(k2, (d_in, d_out), jnp.float32)

    # row-parallel: contraction dim sharded on both operands; output
    # columns end up scattered over the axis
    fn = shard_map(
        functools.partial(C.reduce_scatter_matmul, axis_name="model"),
        mesh=host_mesh,
        in_specs=(P(None, "model"), P("model", None)),
        out_specs=P(None, "model"), check_vma=False)
    got = fn(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# int8 gradient compression with error feedback
# ---------------------------------------------------------------------------
def test_quantize_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,), jnp.float32) * 3.0
    c = Q.quantize(x)
    back = Q.dequantize(c)
    # per-block max / 127 quantization step
    step = 3.0 * 4 / 127          # generous bound on |x|max/127
    assert float(jnp.max(jnp.abs(back - x))) < step
    assert c.q.dtype == jnp.int8


def test_error_feedback_carries_residual():
    g = {"w": jnp.array([0.3, -0.2, 0.7])}
    err = Q.init_error(g)
    comp, err1 = Q.compress_with_feedback(g, err)
    # residual = g - dequant(comp)
    np.testing.assert_allclose(
        np.asarray(err1["w"]),
        np.asarray(g["w"] - Q.dequantize(comp["w"])), rtol=1e-6)


def test_compressed_psum_approximates_mean(host_mesh):
    """Across the data axis, the int8 all-reduce mean tracks the true mean
    within quantization error."""
    n = host_mesh.shape["data"]
    xs = jax.random.normal(jax.random.PRNGKey(2), (n, 512), jnp.float32)

    def body(x):
        comp, _ = Q.compress_with_feedback({"g": x}, {"g": jnp.zeros_like(x)})
        return Q.psum_compressed(comp, "data")["g"]

    fn = shard_map(body, mesh=host_mesh,
                       in_specs=P("data"), out_specs=P("data"),
                       check_vma=False)
    got = fn(xs.reshape(n, -1)).reshape(n, -1)[0]
    want = xs.mean(0).reshape(-1)[: got.shape[0]]
    # mean-scale approximation error is bounded by ~2 quant steps
    scale = float(jnp.max(jnp.abs(xs))) / 127
    assert float(jnp.max(jnp.abs(got - want))) < 4 * scale


def test_error_feedback_converges_running_sum():
    """Repeatedly compressing the same gradient with feedback: the running
    decompressed sum converges to the true sum (unbiasedness)."""
    g = jnp.array([0.01, -0.003, 0.25, 1.7], jnp.float32)
    err = jnp.zeros_like(g)
    total = jnp.zeros_like(g)
    for i in range(50):
        comp, err = Q.compress_with_feedback({"g": g}, {"g": err})
        err = err["g"]
        total = total + Q.dequantize(comp["g"])
    np.testing.assert_allclose(np.asarray(total / 50), np.asarray(g),
                               atol=1e-3)


# ---------------------------------------------------------------------------
# pipeline parallelism (GPipe over 'pod')
# ---------------------------------------------------------------------------
def test_gpipe_matches_sequential(pod_mesh):
    S = pod_mesh.shape["pod"]
    L, d = 4 * S, 16
    ks = jax.random.split(jax.random.PRNGKey(3), L)
    Ws = jnp.stack([jax.random.normal(k, (d, d)) * 0.2 for k in ks])

    def layer_stack(ws, x):          # apply this stage's layers
        def body(xc, w):
            return jnp.tanh(xc @ w), None
        out, _ = jax.lax.scan(body, x, ws)
        return out

    staged = PP.stage_params(Ws, S)
    M, mb = 8, 4
    xs = jax.random.normal(jax.random.PRNGKey(4), (M, mb, d))

    pipelined = PP.gpipe(layer_stack, pod_mesh, axis="pod")
    got = pipelined(staged, xs)

    want = xs
    for i in range(L):
        want = jnp.tanh(want @ Ws[i])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_bubble_fraction():
    assert PP.bubble_fraction(8, 2) == pytest.approx(1 / 9)
    assert PP.bubble_fraction(1, 4) == pytest.approx(3 / 4)
