"""Attention: GQA/MQA/MHA + DeepSeek MLA, training and decode paths.

Three implementations selected by ``cfg.attn_impl``:
  * ``chunked`` — flash-style lax.scan over KV blocks in pure jnp.  O(S·D)
    memory; this is the AOT dry-run path (memory analysis stays honest).
  * ``pallas``  — Pallas TPU kernels (kernels/flash_attention.py,
    kernels/decode_attention.py); validated in interpret mode on CPU.
  * ``naive``   — full S×T score matrix; tiny-shape oracle only.

Keys and values reach the attention functions head-major, (B, H_kv, T, D):
the layout the KV cache stores, so decode reads a layer's cache as it lies.
Every cache is a ring of ``T`` entries with stored absolute positions;
local-attention layers keep ``window`` entries, so gemma2/recurrentgemma
long-context decode memory is O(window), not O(context).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, LOCAL_ATTN
from repro.models import layers as L

NEG_INF = -1.0e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig) -> dict:
    pd = L.pdtype_of(cfg)
    d = cfg.d_model
    if cfg.mla is not None:
        m = cfg.mla
        qd = cfg.num_heads * (m.qk_nope_head_dim + m.qk_rope_head_dim)
        ks = jax.random.split(key, 5)
        return {
            "wq": L.dense_init(ks[0], d, qd, pd),
            "w_dkv": L.dense_init(ks[1], d, m.kv_lora_rank + m.qk_rope_head_dim, pd),
            "kv_norm": jnp.zeros((m.kv_lora_rank,), pd),
            "w_uk": L.dense_init(ks[2], m.kv_lora_rank,
                                 cfg.num_heads * m.qk_nope_head_dim, pd),
            "w_uv": L.dense_init(ks[3], m.kv_lora_rank,
                                 cfg.num_heads * m.v_head_dim, pd),
            "wo": L.dense_init(ks[4], cfg.num_heads * m.v_head_dim, d, pd),
        }
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], d, cfg.q_dim, pd),
        "wk": L.dense_init(ks[1], d, cfg.kv_dim, pd),
        "wv": L.dense_init(ks[2], d, cfg.kv_dim, pd),
        "wo": L.dense_init(ks[3], cfg.q_dim, d, pd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), pd)
        p["bk"] = jnp.zeros((cfg.kv_dim,), pd)
        p["bv"] = jnp.zeros((cfg.kv_dim,), pd)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((cfg.head_dim,), pd)
        p["k_norm"] = jnp.zeros((cfg.head_dim,), pd)
    return p


def init_cross_attention(key, cfg: ModelConfig) -> dict:
    """Whisper decoder cross-attention (always dense MHA, no rope)."""
    pd = L.pdtype_of(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(ks[0], d, cfg.q_dim, pd),
        "wk": L.dense_init(ks[1], d, cfg.q_dim, pd),
        "wv": L.dense_init(ks[2], d, cfg.q_dim, pd),
        "wo": L.dense_init(ks[3], cfg.q_dim, d, pd),
    }


# ---------------------------------------------------------------------------
# core chunked flash-style attention (pure jnp, scan over KV blocks)
# ---------------------------------------------------------------------------
def chunked_attention(q, k, v, q_pos, k_pos, *, scale: float,
                      causal: bool = True, window: int = 0,
                      cap: float = 0.0, chunk: int = 512,
                      k_valid=None, seg_q=None, seg_k=None,
                      q_chunk: int = 4096, layer=None) -> jnp.ndarray:
    """Flash-style attention, pure jnp.  q: (B,S,Hq,Dk); k/v: (B,H,T,D*),
    or with ``layer`` a layer stack (G,B,H,T,D*) of which entry ``layer``
    is read, chunk by chunk, where it lies.

    Long sequences are processed in ``q_chunk`` query blocks (unrolled,
    static shapes).  For the causal self-attention layout (T == S, no
    cache) each query block only multiplies against its *reachable* KV
    prefix — and, for sliding-window layers, only the [lo, hi) KV band —
    so HLO FLOPs stay at the banded/causal count instead of the full S·T
    rectangle.  Within a block, a lax.scan streams KV chunks with an
    online-softmax accumulator (O(S·D) memory).
    """
    B, S, Hq, Dk = q.shape
    T = k.shape[-2]
    if S > q_chunk and S % q_chunk == 0 and q_pos.ndim == 2:
        outs = []
        for i in range(S // q_chunk):
            sl = slice(i * q_chunk, (i + 1) * q_chunk)
            qi, qpi = q[:, sl], q_pos[:, sl]
            sqi = seg_q[:, sl] if seg_q is not None else None
            if causal and k_valid is None and T == S:
                hi = (i + 1) * q_chunk
                lo = max(0, i * q_chunk - window + 1) if window else 0
                lo = (lo // chunk) * chunk          # chunk-aligned band
                ki, vi, kpi = k[:, :, lo:hi], v[:, :, lo:hi], k_pos[:, lo:hi]
                ski = seg_k[:, lo:hi] if seg_k is not None else None
            else:
                ki, vi, kpi, ski = k, v, k_pos, seg_k
            outs.append(_chunked_attention(
                qi, ki, vi, qpi, kpi, scale=scale, causal=causal,
                window=window, cap=cap, chunk=chunk, k_valid=k_valid,
                seg_q=sqi, seg_k=ski, layer=layer))
        return jnp.concatenate(outs, axis=1)
    return _chunked_attention(q, k, v, q_pos, k_pos, scale=scale,
                              causal=causal, window=window, cap=cap,
                              chunk=chunk, k_valid=k_valid, seg_q=seg_q,
                              seg_k=seg_k, layer=layer)


def _kv_chunk(x, i, c: int, layer=None):
    """Entries [i c, (i + 1) c) of keys or values (B,H,T,D), or of entry
    ``layer`` of a stack (G,B,H,T,D): a slice XLA fuses into its reader."""
    if layer is None:
        return jax.lax.dynamic_slice_in_dim(x, i * c, c, axis=2)
    return jax.lax.dynamic_slice(
        x, (layer, 0, 0, i * c, 0), (1,) + x.shape[1:3] + (c,) + x.shape[4:]
    )[0]


def _chunked_attention(q, k, v, q_pos, k_pos, *, scale: float,
                       causal: bool = True, window: int = 0,
                       cap: float = 0.0, chunk: int = 512,
                       k_valid=None, seg_q=None, seg_k=None,
                       layer=None) -> jnp.ndarray:
    """q: (B,S,Hq,Dk), k: (B,Hkv,T,Dk), v: (B,Hkv,T,Dv) (entry ``layer``
    of a stack of them, see ``chunked_attention``).

    q_pos: (B,S) absolute positions of queries; k_pos: (B,T) of keys.
    k_valid: (B,T) bool — entries that exist (cache fill mask).
    Returns (B,S,Hq,Dv).  All accumulation in fp32.
    """
    B, S, Hq, Dk = q.shape
    Hkv, T = k.shape[-3], k.shape[-2]
    Dv = v.shape[-1]
    G = Hq // Hkv

    c = min(chunk, T)
    n_chunks = -(-T // c)
    pad = n_chunks * c - T
    if pad:
        widths = [(0, 0)] * (k.ndim - 2) + [(0, pad), (0, 0)]
        k, v = jnp.pad(k, widths), jnp.pad(v, widths)
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
        kv_mask = jnp.pad(
            jnp.ones((B, T), bool) if k_valid is None else k_valid,
            ((0, 0), (0, pad)))
        if seg_k is not None:
            seg_k = jnp.pad(seg_k, ((0, 0), (0, pad)), constant_values=-2)
    else:
        kv_mask = jnp.ones((B, T), bool) if k_valid is None else k_valid

    qf = (q * jnp.asarray(scale, q.dtype)).reshape(B, S, Hkv, G, Dk)
    pc = k_pos.reshape(B, n_chunks, c)
    mc = kv_mask.reshape(B, n_chunks, c)
    sc = seg_k.reshape(B, n_chunks, c) if seg_k is not None else None

    def body(carry, xs):
        m_run, l_run, acc = carry
        if sc is None:
            i, p_i, valid_i = xs
            s_i = None
        else:
            i, p_i, valid_i, s_i = xs
        # the chunk's keys and values are read where they lie (a KV cache
        # in place), not restacked chunk-major
        k_i, v_i = _kv_chunk(k, i, c, layer), _kv_chunk(v, i, c, layer)
        # scores: (B, S, Hkv, G, c) — bf16 operands, fp32 MXU accumulation
        s = jnp.einsum("bshgd,bhcd->bshgc", qf, k_i,
                       preferred_element_type=jnp.float32)
        if cap:
            s = cap * jnp.tanh(s / cap)
        mask = valid_i[:, None, :]                     # (B,1,c)
        if causal:
            mask = mask & (p_i[:, None, :] <= q_pos[:, :, None])
        if window:
            mask = mask & (q_pos[:, :, None] - p_i[:, None, :] < window)
        if s_i is not None:
            mask = mask & (s_i[:, None, :] == seg_q[:, :, None])
        mask = mask[:, :, None, None, :]               # (B,S,1,1,c)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None]) * mask       # masked probs
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + jnp.sum(p, axis=-1)
        # v first: the CPU backend's bf16 dot runs in this operand order
        acc = acc * corr[..., None] + jnp.einsum(
            "bhcd,bshgc->bshgd", v_i, p.astype(v_i.dtype),
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, S, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, Hkv, G), jnp.float32)
    a0 = jnp.zeros((B, S, Hkv, G, Dv), jnp.float32)
    xs = (jnp.arange(n_chunks), pc.swapaxes(0, 1), mc.swapaxes(0, 1))
    if sc is not None:
        xs = xs + (sc.swapaxes(0, 1),)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), xs)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, S, Hq, Dv).astype(q.dtype)


def naive_attention(q, k, v, q_pos, k_pos, *, scale, causal=True, window=0,
                    cap=0.0, k_valid=None, seg_q=None, seg_k=None):
    """Full-score attention (decode path + tiny-shape oracle).
    q: (B,S,Hq,Dk); k/v: (B,Hkv,T,D*), the cache's own layout.

    No fp32 materialization of k/v: the MXU consumes bf16 operands and
    accumulates fp32 (``preferred_element_type``) — casting the KV cache
    to fp32 would otherwise double decode HBM traffic (EXPERIMENTS.md
    §Perf, decode hillclimb).  Probabilities are cast to v's dtype before
    the PV matmul (standard TPU flash practice; exact when v is fp32).
    """
    B, S, Hq, Dk = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qf = (q * jnp.asarray(scale, q.dtype)).reshape(B, S, Hkv, G, Dk)
    s = jnp.einsum("bshgd,bhtd->bshgt", qf, k,
                   preferred_element_type=jnp.float32)
    if cap:
        s = cap * jnp.tanh(s / cap)
    mask = jnp.ones((B, S, T), bool)
    if k_valid is not None:
        mask = mask & k_valid[:, None, :]
    if causal:
        mask = mask & (k_pos[:, None, :] <= q_pos[:, :, None])
    if window:
        mask = mask & (q_pos[:, :, None] - k_pos[:, None, :] < window)
    if seg_q is not None:
        mask = mask & (seg_k[:, None, :] == seg_q[:, :, None])
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = p * mask[:, :, None, None, :]
    # v first: the CPU backend's bf16 dot runs in this operand order
    out = jnp.einsum("bhtd,bshgt->bshgd", v, p.astype(v.dtype),
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, Hq, -1).astype(q.dtype)


def _run_attention(cfg: ModelConfig, q, k, v, q_pos, k_pos, *, scale, causal,
                   window, cap, k_valid=None, seg_q=None, seg_k=None,
                   layer=None):
    """k/v: (B,Hkv,T,D), or with ``layer`` a layer stack (G,B,Hkv,T,D) of
    which entry ``layer`` is attended: the chunked scan reads it in place,
    the one-query paths through a slice XLA fuses into their dots."""
    decode = q.shape[1] == 1
    kernel = (cfg.attn_impl == "pallas" and seg_q is None
              and k.shape[-1] == v.shape[-1]
              and (decode or (k_valid is None and q.shape[1] == k.shape[-2])))
    if not (kernel or cfg.attn_impl == "naive" or decode):
        return chunked_attention(q, k, v, q_pos, k_pos, scale=scale,
                                 causal=causal, window=window, cap=cap,
                                 chunk=cfg.attn_chunk, k_valid=k_valid,
                                 seg_q=seg_q, seg_k=seg_k, layer=layer)
    if layer is not None:
        k, v = (jax.lax.dynamic_index_in_dim(x, layer, 0, keepdims=False)
                for x in (k, v))
    if kernel:
        from repro.kernels import ops as kops
        if decode:
            return kops.decode_attention(
                q, k, v, q_pos[:, 0], scale=scale, window=window,
                cap=cap, interpret=kops.on_cpu())
        return kops.flash_attention(
            q, k, v, scale=scale, causal=causal, window=window,
            cap=cap, interpret=kops.on_cpu())
    # decode (one query): the full-score einsum IS flash-decode FLOPs-wise,
    # shards cleanly over a length- or head-partitioned cache (psum'd
    # softmax reductions), and avoids lax.scan over a sharded KV axis.
    return naive_attention(q, k, v, q_pos, k_pos, scale=scale,
                           causal=causal, window=window, cap=cap,
                           k_valid=k_valid, seg_q=seg_q, seg_k=seg_k)


# ---------------------------------------------------------------------------
# KV cache helpers
# ---------------------------------------------------------------------------
def init_kv_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int) -> dict:
    """Zeroed cache pytree for one attention layer: k/v (B, H_kv, T, D),
    head-major as attention reads them; MLA's ckv/krope (B, T, r) and the
    positions (B, T), which have no head dim.  -1 marks an empty entry."""
    dt = L.dtype_of(cfg)
    size = min(max_len, cfg.window_size) if (kind == LOCAL_ATTN and cfg.window_size) else max_len
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "ckv": jnp.zeros((batch, size, m.kv_lora_rank), dt),
            "krope": jnp.zeros((batch, size, m.qk_rope_head_dim), dt),
            "pos": jnp.full((batch, size), -1, jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, cfg.num_kv_heads, size, cfg.head_dim), dt),
        "v": jnp.zeros((batch, cfg.num_kv_heads, size, cfg.head_dim), dt),
        "pos": jnp.full((batch, size), -1, jnp.int32),
    }


def _ring_put(buf, new, offsets, valid, axis: int, layer=None):
    """``buf`` with ``new`` (B, ..., S, ...) written along ``axis`` at the
    entries (offsets[b] + i) mod T of each row b.

    ``buf`` is one layer's leaf (B, ..., T, ...), or with ``layer`` a leaf
    of a layer stack (G, B, ..., T, ...) and ``layer`` the index into it.
    Every write is a ``dynamic_update_slice`` of one row's entries, so XLA
    updates the buffer in place and keeps its layout.  One entry (decode)
    is one update at offset mod T, pad or not: an inactive slot's entry
    there takes position -1, and the slot's next entry is written over it
    (a masked one-entry write makes XLA relay the whole cache out for
    attention).  S > 1 entries write only where ``valid`` (B, S): what the
    ring held under a pad entry stays.  They go through two windows of S
    entries, each read, merged and written back: one from min(offset mod
    T, T - S), and one from 0, which takes what wraps past T (and is
    written back unchanged when nothing wraps).  More entries than the
    ring holds are written T at a time, in order.
    """
    lead = () if layer is None else (layer,)
    ax = len(lead) + axis
    T, S = buf.shape[ax], new.shape[axis]
    if S > T:
        for lo in range(0, S, T):
            part = jax.lax.slice_in_dim(new, lo, min(lo + T, S), axis=axis)
            buf = _ring_put(buf, part, offsets + lo, valid[:, lo:lo + T],
                            axis, layer)
        return buf
    size = (1,) * len(lead) + (1,) + new.shape[1:]
    new = new.astype(buf.dtype)

    def at(b, w):
        idx = list(lead) + [0] * (len(size) - len(lead))
        idx[len(lead)], idx[ax] = b, w
        return tuple(idx)

    mask_shape = [1] * len(size)
    mask_shape[ax] = S
    for b in range(new.shape[0]):
        row = jax.lax.slice_in_dim(new, b, b + 1, axis=0).reshape(size)
        a = offsets[b] % T
        if S == 1:
            buf = jax.lax.dynamic_update_slice(buf, row, at(b, a))
            continue
        # entry w + j of a window takes new[w + j - a] from a up to T, and
        # new[w + j - a + T] below a + S - T: ``row`` rolled by a - w, or
        # by a - w - T
        w = jnp.minimum(a, T - S)
        j = jnp.arange(S)
        for w, shift, hit in ((w, a - w, w + j >= a),
                              (0, a - T, j < a + S - T)):
            hit = hit & jnp.roll(valid[b], shift)
            old = jax.lax.dynamic_slice(buf, at(b, w), size)
            buf = jax.lax.dynamic_update_slice(
                buf, jnp.where(hit.reshape(mask_shape),
                               jnp.roll(row, shift, axis=ax), old),
                at(b, w))
    return buf


def update_cache(cache: dict, new: dict, offsets: jnp.ndarray,
                 positions: jnp.ndarray, layer=None) -> dict:
    """``new``: dict of rows in their leaves' layouts, S along the token
    axis; positions: (B,S) absolute positions, -1 for pad entries, which
    are not written.  ``layer`` indexes a layer stack (``_ring_put``)."""
    out = dict(cache)
    valid = positions >= 0
    for name, val in {**new, "pos": positions.astype(jnp.int32)}.items():
        axis = 2 if name in ("k", "v") else 1       # the length axis
        out[name] = _ring_put(cache[name], val, offsets, valid, axis, layer)
    return out


def layer_slice(cache: dict, layer=None) -> dict:
    """One layer's leaves of a stacked cache (``cache`` itself if
    ``layer`` is None)."""
    if layer is None:
        return cache
    return {n: jax.lax.dynamic_index_in_dim(x, layer, 0, keepdims=False)
            for n, x in cache.items()}


# ---------------------------------------------------------------------------
# standard GQA attention layer
# ---------------------------------------------------------------------------
def attention_layer(params: dict, x: jnp.ndarray, positions: jnp.ndarray,
                    cfg: ModelConfig, kind: str,
                    cache: Optional[dict] = None,
                    cache_offset: Optional[jnp.ndarray] = None,
                    seg: Optional[jnp.ndarray] = None,
                    causal: bool = True, layer=None,
                    ) -> Tuple[jnp.ndarray, Optional[dict]]:
    """x: (B,S,d).  Train/prefill: cache None or appended-to.  Decode: S small
    (usually 1), cache required.  positions: (B,S) or (3,B,S) for M-RoPE.
    ``layer``: ``cache`` is a layer stack and this layer is its entry
    ``layer``; only this call's entries of it are written."""
    if cfg.mla is not None:
        return _mla_layer(params, x, positions, cfg, cache, cache_offset,
                          layer)
    dt = x.dtype
    B, S, _ = x.shape
    pos2d = positions if positions.ndim == 2 else positions[0]
    q = x @ params["wq"].astype(dt)
    k = x @ params["wk"].astype(dt)
    v = x @ params["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = L.rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, params["k_norm"], cfg.norm_eps)
    if cfg.use_rope:
        angles = L.rope_angles(positions, cfg.head_dim, cfg.rope_theta,
                               cfg.mrope_sections)
        q = L.apply_rope(q, angles)
        k = L.apply_rope(k, angles)

    scale = cfg.attn_scale or (1.0 / math.sqrt(cfg.head_dim))
    window = cfg.window_size if kind == LOCAL_ATTN else 0

    k, v = k.swapaxes(1, 2), v.swapaxes(1, 2)          # (B, H_kv, S, D)
    if cache is None:
        out = _run_attention(cfg, q, k, v, pos2d, pos2d, scale=scale,
                             causal=causal, window=window,
                             cap=cfg.attn_softcap, seg_q=seg, seg_k=seg)
    else:
        cache = update_cache(cache, {"k": k, "v": v}, cache_offset, pos2d,
                             layer)
        k_pos = layer_slice(cache, layer)["pos"]
        out = _run_attention(cfg, q, cache["k"], cache["v"], pos2d, k_pos,
                             scale=scale, causal=causal, window=window,
                             cap=cfg.attn_softcap, k_valid=k_pos >= 0,
                             layer=layer)
    out = out.reshape(B, S, cfg.q_dim) @ params["wo"].astype(dt)
    return out, cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): expanded for train/prefill, absorbed-MQA for decode
# ---------------------------------------------------------------------------
def _mla_project_q(params, x, cfg, positions):
    m = cfg.mla
    B, S, _ = x.shape
    dt = x.dtype
    q = (x @ params["wq"].astype(dt)).reshape(
        B, S, cfg.num_heads, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    angles = L.rope_angles(positions, m.qk_rope_head_dim, cfg.rope_theta)
    q_rope = L.apply_rope(q_rope, angles)
    return q_nope, q_rope, angles


def _mla_latent(params, x, cfg, angles):
    m = cfg.mla
    dt = x.dtype
    ckr = x @ params["w_dkv"].astype(dt)
    ckv, k_rope = ckr[..., :m.kv_lora_rank], ckr[..., m.kv_lora_rank:]
    ckv = L.rms_norm(ckv, params["kv_norm"], cfg.norm_eps)
    k_rope = L.apply_rope(k_rope[:, :, None, :], angles)[:, :, 0, :]
    return ckv, k_rope


def _mla_layer(params, x, positions, cfg, cache, cache_offset, layer=None):
    m = cfg.mla
    B, S, _ = x.shape
    dt = x.dtype
    pos2d = positions if positions.ndim == 2 else positions[0]
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope, angles = _mla_project_q(params, x, cfg, positions)
    ckv, k_rope = _mla_latent(params, x, cfg, angles)

    w_uk = params["w_uk"].astype(dt).reshape(
        m.kv_lora_rank, cfg.num_heads, m.qk_nope_head_dim)
    w_uv = params["w_uv"].astype(dt).reshape(
        m.kv_lora_rank, cfg.num_heads, m.v_head_dim)

    if cache is None:
        # expanded path: materialize per-head k/v from the latent
        k_nope = jnp.einsum("btr,rhd->bthd", ckv, w_uk)
        v = jnp.einsum("btr,rhd->bthd", ckv, w_uv)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (B, S, cfg.num_heads, m.qk_rope_head_dim))],
            axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = _run_attention(cfg, q, k.swapaxes(1, 2), v.swapaxes(1, 2),
                             pos2d, pos2d, scale=scale,
                             causal=True, window=0, cap=0.0)
        new_cache = None
    else:
        # absorbed path: attention in latent space == MQA with Dk=rank+rope,
        # Dv=rank.  Cache stores only (ckv, k_rope): the MLA memory win.
        new_cache = update_cache(cache, {"ckv": ckv, "krope": k_rope},
                                 cache_offset, pos2d, layer)
        c = layer_slice(new_cache, layer)
        q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)
        q_abs = jnp.concatenate([q_lat, q_rope], axis=-1)
        k_abs = jnp.concatenate([c["ckv"], c["krope"]], axis=-1)[:, None]
        v_abs = c["ckv"][:, None]                   # one head: (B, 1, T, r)
        ctx = _run_attention(cfg, q_abs, k_abs, v_abs, pos2d, c["pos"],
                             scale=scale, causal=True, window=0, cap=0.0,
                             k_valid=c["pos"] >= 0)
        out = jnp.einsum("bshr,rhd->bshd", ctx, w_uv)
    out = out.reshape(B, S, cfg.num_heads * m.v_head_dim)
    return out @ params["wo"].astype(dt), new_cache


# ---------------------------------------------------------------------------
# cross attention (whisper decoder)
# ---------------------------------------------------------------------------
def cross_attention_layer(params, x, enc_kv, cfg: ModelConfig):
    """enc_kv: (k, v) precomputed from encoder output, (B,H,T,D)."""
    dt = x.dtype
    B, S, _ = x.shape
    k, v = enc_kv
    q = (x @ params["wq"].astype(dt)).reshape(B, S, cfg.num_heads, cfg.head_dim)
    T = k.shape[2]
    scale = 1.0 / math.sqrt(cfg.head_dim)
    pos_q = jnp.zeros((B, S), jnp.int32)
    pos_k = jnp.zeros((B, T), jnp.int32)
    out = _run_attention(cfg, q, k, v, pos_q, pos_k, scale=scale,
                         causal=False, window=0, cap=0.0)
    return out.reshape(B, S, cfg.q_dim) @ params["wo"].astype(dt)


def encode_cross_kv(params, enc_out, cfg: ModelConfig):
    """Cross K/V of the encoder output, (B,H,T,D) each."""
    dt = enc_out.dtype
    B, T, _ = enc_out.shape
    k = (enc_out @ params["wk"].astype(dt)).reshape(B, T, cfg.num_heads, cfg.head_dim)
    v = (enc_out @ params["wv"].astype(dt)).reshape(B, T, cfg.num_heads, cfg.head_dim)
    return k.swapaxes(1, 2), v.swapaxes(1, 2)
