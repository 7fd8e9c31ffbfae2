"""Plain float32 Qwen3 decoder and the benchmark's seeded weights.

The forward follows the published layer equations (Hugging Face
``modeling_qwen3``): token embedding; per layer ``h += o_proj(attn(
rope(q_norm(q_proj(n))), rope(k_norm(k_proj(n))), v_proj(n)))`` with
``n = rmsnorm(h) * input_layernorm`` and grouped-query heads (query head
``j`` reads key/value head ``j // (heads / kv_heads)``), causal softmax
scaled by ``head_dim ** -0.5``, rotate-half RoPE with ``inv_freq =
theta ** (-2i / head_dim)``; then ``h += down_proj(silu(gate_proj(m)) *
up_proj(m))`` with ``m = rmsnorm(h) * post_attention_layernorm``; the
final ``norm`` and an untied ``lm_head``.  RMSNorm is ``x * rsqrt(mean(
x^2) + eps)``.  Every matmul runs at ``precision="highest"``; no kernel,
cache or batching of the program is used.  Layers run one at a time, so
the reference fits beside nothing else on one chip.

Weights are the benchmark's, drawn from the seed (``leaf``): matrices
``N(0, 1/fan_in)``, the embedding ``N(0, 1)``, each norm weight ``1 +
delta`` with ``delta ~ N(0, 0.1^2)``, except the query and key norms,
whose weights are ``1.5 + delta``: attention scores then spread over
about ``N(0, 5)``, so a token weighs some keys of its cache far above
the rest and what the cache holds moves its logits.  Sharper attention
(weights ``2 + delta``, scores ``N(0, 16)``) makes 16 bfloat16 layers
chaotic: near-ties between keys flip on rounding, and served tokens of
a sound bfloat16 run then lie as far below the reference's best as the
float8 control's (PERF.md).  Every leaf is rounded to the served dtype
(bfloat16), and the reference computes with those values in float32.
The program is handed the same leaves in its own layout.

``logits(..., fp8=True)`` is the precision control: the same forward with
every projection computed in float8 (e4m3, scaled per output channel for
weights and per token for activations), the step below the served
bfloat16.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("input_layernorm", "q_proj", "k_proj", "v_proj", "o_proj",
                "q_norm", "k_norm", "post_attention_layernorm",
                "gate_proj", "up_proj", "down_proj")
GLOBAL_LEAVES = ("embed_tokens", "norm", "lm_head")
NORMS = ("input_layernorm", "q_norm", "k_norm", "post_attention_layernorm",
         "norm")
LEAF_ID = {n: i for i, n in enumerate(LAYER_LEAVES + GLOBAL_LEAVES)}
ROW_BLOCKS = 16          # matrices are drawn in this many row blocks
NORM_STD = 0.1
QK_NORM_SHIFT = 0.5      # q_norm / k_norm weights ~ 1.5


def shapes(c: dict) -> Dict[str, tuple]:
    d, hd, ff = c["hidden_size"], c["head_dim"], c["intermediate_size"]
    q = c["num_attention_heads"] * hd
    kv = c["num_key_value_heads"] * hd
    return {"input_layernorm": (d,), "q_proj": (d, q), "k_proj": (d, kv),
            "v_proj": (d, kv), "o_proj": (q, d), "q_norm": (hd,),
            "k_norm": (hd,), "post_attention_layernorm": (d,),
            "gate_proj": (d, ff), "up_proj": (d, ff), "down_proj": (ff, d),
            "embed_tokens": (c["vocab_size"], d), "norm": (d,),
            "lm_head": (d, c["vocab_size"])}


def root_key(seed: int) -> np.ndarray:
    """Threefry key data of a seed of up to 64 bits."""
    s = int(seed) & (2 ** 64 - 1)
    return np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)


def leaf(key_data, name: str, layer, shape: Sequence[int], dtype):
    """One weight leaf in ``dtype``; ``layer`` may be traced.  Norm leaves
    hold ``delta``, the weight minus one."""
    key = jax.random.wrap_key_data(jnp.asarray(key_data, jnp.uint32))
    key = jax.random.fold_in(jax.random.fold_in(key, LEAF_ID[name]), layer)
    if name in NORMS:
        shift = QK_NORM_SHIFT if name in ("q_norm", "k_norm") else 0.0
        return (shift + NORM_STD * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    rows, cols = shape
    nb = ROW_BLOCKS if rows % ROW_BLOCKS == 0 else 1
    std = 1.0 if name == "embed_tokens" else 1.0 / math.sqrt(rows)

    def block(b):
        kb = jax.random.fold_in(key, b)
        return (std * jax.random.normal(kb, (rows // nb, cols), jnp.float32)
                ).astype(dtype)
    return jax.lax.map(block, jnp.arange(nb)).reshape(rows, cols)


def layer_weights(c: dict, key_data, layer: int, dtype) -> Dict[str, jax.Array]:
    sh = shapes(c)
    return {n: leaf(key_data, n, layer, sh[n], dtype) for n in LAYER_LEAVES}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (B, L, H, D); rotate-half RoPE at integer positions pos (B, L)."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos.astype(jnp.float32)[..., None] * inv          # (B, L, D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


FP8_MAX = 448.0          # largest finite float8_e4m3fn


def _fp8(a, axis):
    s = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True),
                    1e-30) / FP8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32), s


def _fp8_matmul(x, w):
    """x @ w with x scaled per row and w per column into float8 e4m3."""
    xq, sx = _fp8(x, -1)
    wq, sw = _fp8(w, 0)
    return (xq @ wq) * sx * sw


def _layer(c: dict, w: dict, h, pos, valid, fp8: bool):
    mm = _fp8_matmul if fp8 else (lambda a, b: a @ b)
    eps = c["rms_norm_eps"]
    B, L, d = h.shape
    H, KV, D = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    G = H // KV
    f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    n = _rms(h, 1.0 + f32["input_layernorm"], eps)
    q = mm(n, f32["q_proj"]).reshape(B, L, H, D)
    k = mm(n, f32["k_proj"]).reshape(B, L, KV, D)
    v = mm(n, f32["v_proj"]).reshape(B, L, KV, D)
    q = _rope(_rms(q, 1.0 + f32["q_norm"], eps), pos, c["rope_theta"])
    k = _rope(_rms(k, 1.0 + f32["k_norm"], eps), pos, c["rope_theta"])
    causal = (pos[:, None, :] <= pos[:, :, None]) & valid[:, None, :]

    def head_group(g):
        qg = jax.lax.dynamic_slice_in_dim(q, g * G, G, axis=2)   # (B,L,G,D)
        kg = k[:, :, g]
        vg = v[:, :, g]
        s = jnp.einsum("blgd,bmd->bglm", qg, kg) / math.sqrt(D)
        s = jnp.where(causal[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bglm,bmd->blgd", p, vg)

    o = jax.lax.map(head_group, jnp.arange(KV))                  # (KV,B,L,G,D)
    o = jnp.moveaxis(o, 0, 2).reshape(B, L, H * D)
    h = h + mm(o, f32["o_proj"])
    m = _rms(h, 1.0 + f32["post_attention_layernorm"], eps)
    y = jax.nn.silu(mm(m, f32["gate_proj"])) * mm(m, f32["up_proj"])
    return h + mm(y, f32["down_proj"])


def _forward_fns(c: dict, dtype, fp8: bool):
    wdt = jnp.dtype(dtype)

    @jax.jit
    def embed(key_data, tokens):
        table = leaf(key_data, "embed_tokens", 0, shapes(c)["embed_tokens"],
                     wdt)
        return jnp.take(table, tokens, axis=0).astype(jnp.float32)

    @jax.jit
    def layer(key_data, l, h, pos, valid):
        return _layer(c, layer_weights(c, key_data, l, wdt), h, pos, valid,
                      fp8)

    @jax.jit
    def head(key_data, rows):
        sh = shapes(c)
        nw = 1.0 + leaf(key_data, "norm", 0, sh["norm"], wdt).astype(jnp.float32)
        lm = leaf(key_data, "lm_head", 0, sh["lm_head"], wdt).astype(jnp.float32)
        x = _rms(rows, nw, c["rms_norm_eps"])
        return _fp8_matmul(x, lm) if fp8 else x @ lm

    return embed, layer, head


def logits(c: dict, seed: int, seqs: List[np.ndarray], positions: List[np.ndarray],
           dtype="bfloat16", fp8: bool = False) -> List[np.ndarray]:
    """Logits (float32, full vocabulary) of each sequence at the given
    positions.  Sequences are padded to one length and run together,
    layer by layer, with weights drawn per layer from ``seed``."""
    key_data = root_key(seed)
    Lp = max(len(s) for s in seqs)
    Lp = -(-Lp // 128) * 128
    B = len(seqs)
    tokens = np.zeros((B, Lp), np.int32)
    valid = np.zeros((B, Lp), bool)
    for b, s in enumerate(seqs):
        tokens[b, :len(s)] = s
        valid[b, :len(s)] = True
    pos = np.broadcast_to(np.arange(Lp, dtype=np.int32), (B, Lp))
    with jax.default_matmul_precision("highest"):
        embed, layer, head = _forward_fns(c, dtype, fp8)
        h = embed(key_data, tokens)
        for l in range(c["num_hidden_layers"]):
            h = layer(key_data, np.int32(l), h, pos, valid)
        idx_b = np.concatenate([np.full(len(p), b) for b, p in enumerate(positions)])
        idx_l = np.concatenate(positions)
        rows = h[idx_b, idx_l]
        out = np.asarray(head(key_data, rows))
    splits = np.cumsum([len(p) for p in positions])[:-1]
    return np.split(out, splits)


def served_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each served token's logit lies below the reference's best."""
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(tokens)), tokens]
