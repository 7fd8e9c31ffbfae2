"""Public jit'd wrappers around the Pallas TPU kernels.

Layout adapters fold model-layout tensors (queries (B,S,H,D), keys and
values head-major (B,H,T,D), as the KV cache holds them) into the
kernel-native folded layouts, dispatch to pl.pallas_call, and restore the
model layout.  ``interpret=True`` (automatic on CPU via ``on_cpu()``) runs
the kernel bodies in the Pallas interpreter — the correctness path used by
tests/test_kernels.py against the pure-jnp oracles in ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import decode_attention_folded
from repro.kernels.flash_attention import flash_attention_folded
from repro.kernels.rglru_scan import rglru_scan as _rglru_scan
from repro.kernels.ssd_scan import ssd_scan_folded


@functools.lru_cache(None)
def on_cpu() -> bool:
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def flash_attention(q, k, v, *, scale: float, causal: bool = True,
                    window: int = 0, cap: float = 0.0, bq: int = 128,
                    bk: int = 128, interpret: bool = False) -> jnp.ndarray:
    """q: (B,S,Hq,D); k/v: (B,Hkv,T,D) -> (B,S,Hq,D).  GQA folded: query
    heads of one KV head become extra query rows (position-major)."""
    B, S, Hq, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qf = (q.reshape(B, S, Hkv, G, D)
          .transpose(0, 2, 1, 3, 4)          # (B,Hkv,S,G,D)
          .reshape(B * Hkv, S * G, D))
    kf = k.reshape(B * Hkv, T, D)
    vf = v.reshape(B * Hkv, T, D)
    out = flash_attention_folded(qf, kf, vf, groups=G, scale=scale,
                                 causal=causal, window=window, cap=cap,
                                 bq=bq, bk=bk, interpret=interpret)
    return (out.reshape(B, Hkv, S, G, D)
            .transpose(0, 2, 1, 3, 4)
            .reshape(B, S, Hq, D))


def decode_attention(q, k, v, lengths, *, scale: float, window: int = 0,
                     cap: float = 0.0, bk: int = 512,
                     interpret: bool = False) -> jnp.ndarray:
    """q: (B,1,Hq,D); k/v: (B,Hkv,T,D), the KV cache's layout; lengths:
    (B,) -> (B,1,Hq,D)."""
    B, _, Hq, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qf = q.reshape(B, Hkv, G, D).reshape(B * Hkv, G, D)
    kf = k.reshape(B * Hkv, T, D)
    vf = v.reshape(B * Hkv, T, D)
    lens = jnp.repeat(lengths.astype(jnp.int32), Hkv)
    out = decode_attention_folded(qf, kf, vf, lens, scale=scale,
                                  window=window, cap=cap, bk=bk,
                                  interpret=interpret)
    return out.reshape(B, Hkv, G, D).reshape(B, 1, Hq, D)


# ---------------------------------------------------------------------------
# ssd / rglru
# ---------------------------------------------------------------------------
def ssd_scan(x, dt, A_log, B_mat, C_mat, *, chunk: int = 128,
             interpret: bool = False):
    """Model layout: x (B,S,H,P); dt (B,S,H); A_log (H,); B/C (B,S,G,N).
    Returns (y (B,S,H,P), final_state (B,H,P,N)) — matches ssd_scan_ref."""
    Bb, S, H, Pd = x.shape
    G, N = B_mat.shape[2], B_mat.shape[3]
    rep = H // G
    A = -jnp.exp(A_log.astype(jnp.float32))                    # (H,)
    xf = x.transpose(0, 2, 1, 3).reshape(Bb * H, S, Pd)
    dtf = dt.transpose(0, 2, 1).reshape(Bb * H, S).astype(jnp.float32)
    Bf = (jnp.repeat(B_mat, rep, axis=2).transpose(0, 2, 1, 3)
          .reshape(Bb * H, S, N).astype(jnp.float32))
    Cf = (jnp.repeat(C_mat, rep, axis=2).transpose(0, 2, 1, 3)
          .reshape(Bb * H, S, N).astype(jnp.float32))
    Af = jnp.tile(A, Bb)
    y, last = ssd_scan_folded(xf, dtf, Af, Bf, Cf, chunk=chunk,
                              interpret=interpret)
    y = y.reshape(Bb, H, S, Pd).transpose(0, 2, 1, 3)
    return y.astype(x.dtype), last.reshape(Bb, H, Pd, N)


def rglru_scan(a, b, h0=None, *, interpret: bool = False):
    """a, b: (B,S,W) fp32 -> (h (B,S,W), h_last (B,W))."""
    return _rglru_scan(a.astype(jnp.float32), b.astype(jnp.float32), h0,
                       interpret=interpret)
