"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,Hq,Hkv,D,win,cap", [
    (128, 4, 4, 64, 0, 0.0),       # MHA
    (256, 8, 2, 64, 0, 0.0),       # GQA 4:1
    (192, 4, 1, 128, 0, 0.0),      # MQA, unaligned S
    (256, 8, 4, 64, 96, 0.0),      # sliding window
    (128, 4, 4, 64, 0, 50.0),      # gemma2 softcap
    (320, 2, 2, 32, 64, 30.0),     # window + cap + unaligned
])
def test_flash_attention_sweep(dtype, S, Hq, Hkv, D, win, cap):
    ks = jax.random.split(jax.random.PRNGKey(S + Hq), 3)
    B = 2
    q = _rand(ks[0], (B, S, Hq, D), dtype)
    k = _rand(ks[1], (B, S, Hkv, D), dtype)
    v = _rand(ks[2], (B, S, Hkv, D), dtype)
    scale = 1.0 / np.sqrt(D)
    out = ops.flash_attention(q, k.swapaxes(1, 2), v.swapaxes(1, 2),
                              scale=scale, causal=True, window=win,
                              cap=cap, bq=64, bk=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, scale=scale, causal=True,
                                   window=win, cap=cap)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    assert err < TOL[dtype], err


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (1, 64, 2, 32), jnp.float32)
    k = _rand(ks[1], (1, 96, 2, 32), jnp.float32)
    v = _rand(ks[2], (1, 96, 2, 32), jnp.float32)
    out = ops.flash_attention(q, k.swapaxes(1, 2), v.swapaxes(1, 2),
                              scale=0.2, causal=False, interpret=True)
    # non-causal oracle: plain softmax over all keys
    s = jnp.einsum("bshd,bthd->bhst", q * 0.2, k)
    p = jax.nn.softmax(s, -1)
    want = jnp.einsum("bhst,bthd->bshd", p, v)
    assert float(jnp.max(jnp.abs(out - want))) < 2e-5


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("T,Hq,Hkv,D,win,bk", [
    (256, 8, 8, 64, 0, 128),
    (300, 8, 2, 64, 0, 128),       # GQA + unaligned T
    (512, 4, 1, 128, 128, 256),    # MQA + window
    (64, 2, 2, 32, 0, 512),        # bk > T
])
def test_decode_attention_sweep(dtype, T, Hq, Hkv, D, win, bk):
    ks = jax.random.split(jax.random.PRNGKey(T + D), 3)
    B = 3
    q = _rand(ks[0], (B, 1, Hq, D), dtype)
    k = _rand(ks[1], (B, T, Hkv, D), dtype)
    v = _rand(ks[2], (B, T, Hkv, D), dtype)
    lens = jnp.array([1, T // 2, T], jnp.int32)
    scale = 1.0 / np.sqrt(D)
    out = ops.decode_attention(q, k.swapaxes(1, 2), v.swapaxes(1, 2), lens,
                               scale=scale, window=win, bk=bk,
                               interpret=True)
    want = ref.decode_attention_ref(q, k, v, lens, scale=scale, window=win)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    assert err < TOL[dtype], err


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,H,P,G,N,chunk", [
    (128, 4, 32, 1, 16, 64),
    (200, 4, 32, 2, 16, 64),       # groups + ragged chunks
    (96, 2, 64, 1, 32, 32),
])
def test_ssd_scan_sweep(dtype, S, H, P, G, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(S + N), 4)
    B = 2
    x = _rand(ks[0], (B, S, H, P), dtype) * 0.5
    dt = jax.nn.softplus(_rand(ks[1], (B, S, H), jnp.float32))
    A_log = jnp.log(jnp.linspace(1.0, 4.0, H))
    Bm = _rand(ks[2], (B, S, G, N), jnp.float32) * 0.3
    Cm = _rand(ks[3], (B, S, G, N), jnp.float32) * 0.3
    y, st = ops.ssd_scan(x, dt, A_log, Bm, Cm, chunk=chunk, interpret=True)
    yr, sr = ref.ssd_scan_ref(x, dt, A_log, Bm, Cm)
    ey = float(jnp.max(jnp.abs(y.astype(jnp.float32)
                               - yr.astype(jnp.float32))))
    es = float(jnp.max(jnp.abs(st - sr)))
    tol = 1e-3 if dtype == jnp.float32 else 5e-2
    assert ey < tol and es < tol, (ey, es)


def test_ssd_scan_matches_model_chunked_path():
    """Kernel == the model's lax.scan SSD implementation (ssm.ssd_chunked)."""
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    B, S, H, P, G, N = 2, 128, 4, 32, 1, 16
    x = _rand(ks[0], (B, S, H, P), jnp.float32) * 0.5
    dt = jax.nn.softplus(_rand(ks[1], (B, S, H), jnp.float32))
    A_log = jnp.log(jnp.linspace(1.0, 4.0, H))
    Bm = _rand(ks[2], (B, S, G, N), jnp.float32) * 0.3
    Cm = _rand(ks[3], (B, S, G, N), jnp.float32) * 0.3
    y1, s1 = ops.ssd_scan(x, dt, A_log, Bm, Cm, chunk=64, interpret=True)
    y2, s2 = ssd_chunked(x, dt, A_log, Bm, Cm, chunk=64)
    assert float(jnp.max(jnp.abs(y1 - y2))) < 1e-3
    assert float(jnp.max(jnp.abs(s1 - s2))) < 1e-3


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("S,W,bs,bw", [
    (128, 128, 64, 128),
    (100, 96, 256, 128),           # padding both dims
    (64, 256, 32, 64),             # multiple width tiles
])
def test_rglru_scan_sweep(S, W, bs, bw):
    ks = jax.random.split(jax.random.PRNGKey(S + W), 3)
    B = 2
    a = jax.nn.sigmoid(_rand(ks[0], (B, S, W), jnp.float32))
    b = _rand(ks[1], (B, S, W), jnp.float32) * 0.1
    h0 = _rand(ks[2], (B, W), jnp.float32)
    h, hl = ops.rglru_scan(a, b, h0, interpret=True)
    hr, hlr = ref.rglru_scan_ref(a, b, h0)
    assert float(jnp.max(jnp.abs(h - hr))) < 1e-5
    assert float(jnp.max(jnp.abs(hl - hlr))) < 1e-5


def test_rglru_kernel_matches_model_scan():
    """Kernel == the model's associative_scan implementation."""
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    B, S, W = 2, 64, 128
    a = jax.nn.sigmoid(_rand(ks[0], (B, S, W), jnp.float32))
    b = _rand(ks[1], (B, S, W), jnp.float32) * 0.1

    def combine(l, r):
        return (r[0] * l[0], r[0] * l[1] + r[1])
    A, Bc = jax.lax.associative_scan(combine, (a, b), axis=1)
    h, _ = ops.rglru_scan(a, b, interpret=True)
    assert float(jnp.max(jnp.abs(h - Bc))) < 1e-4
