"""Operations and bytes a step requires, from shapes alone, and the
chip's peaks to hold them against.

The arithmetic is that of ``model_flops`` in ``benchmarks/roofline.py``
(2 FLOPs per weight per token, 2 * 2 * ctx * heads * head_dim for
attention), read from the published ``config.json`` keys and counted
per call.  ``prefill_work`` and ``decode_work`` count the work the valid
tokens of one serving call require, whatever the program computes
besides:

* matmuls: 2 FLOPs per weight per token, every layer;
* attention: QK^T and PV over the token's real context (2 * 2 * ctx *
  heads * head_dim per layer), causal;
* the head: one row of logits per sequence that emits a token;
* bytes: every weight once per call (bf16), the KV rows each sequence
  reads and the KV rows it writes.

Padding rows, padded positions and full-vocabulary logits at positions
that emit nothing are not required work and are not counted.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Iterable, Tuple

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """The device kind has no row in ``peaks.json``."""


def peaks(device_kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"{PEAKS}")
    return table[device_kind]


def dtype_bytes(name: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[name]


def layer_params(c: dict) -> int:
    """Matmul weights of one decoder layer (attention + SwiGLU)."""
    d, hd = c["hidden_size"], c["head_dim"]
    q = c["num_attention_heads"] * hd
    kv = c["num_key_value_heads"] * hd
    return d * (q + 2 * kv) + q * d + 3 * d * c["intermediate_size"]


def weight_bytes(c: dict) -> int:
    """Weights read once per call: layers, final norm and the head.  The
    embedding table is gathered row by row and counted per token."""
    b = dtype_bytes(c["torch_dtype"])
    d = c["hidden_size"]
    per_layer = layer_params(c) + 2 * d + 2 * c["head_dim"]
    return b * (c["num_hidden_layers"] * per_layer + d
                + d * c["vocab_size"])


def _attn_flops(c: dict, ctx: float) -> float:
    return 2.0 * 2.0 * ctx * c["num_attention_heads"] * c["head_dim"]


def _kv_row_bytes(c: dict) -> int:
    return (2 * c["num_key_value_heads"] * c["head_dim"]
            * dtype_bytes(c["torch_dtype"]) * c["num_hidden_layers"])


def prefill_work(c: dict, rows: Iterable[Tuple[int, int]]) -> Tuple[float, float]:
    """``rows``: ``(context already cached, valid tokens this chunk)`` of
    each sequence the call advances.  Returns ``(flops, bytes)``."""
    L, d, V = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    P = layer_params(c)
    tb = dtype_bytes(c["torch_dtype"])
    flops = 0.0
    nbytes = float(weight_bytes(c))
    any_row = False
    for start, n in rows:
        if n <= 0:
            continue
        any_row = True
        flops += 2.0 * P * L * n
        # token at absolute position p attends to p + 1 keys
        ctx_sum = n * start + n * (n + 1) / 2.0
        flops += L * _attn_flops(c, 1.0) * ctx_sum
        flops += 2.0 * d * V                      # the last token's logits
        nbytes += _kv_row_bytes(c) * (start + n)  # read cache + written rows
        nbytes += n * d * tb                      # embedding rows
    return (flops, nbytes) if any_row else (0.0, 0.0)


def decode_work(c: dict, contexts: Iterable[int]) -> Tuple[float, float]:
    """``contexts``: cached length of each active sequence before its
    token.  Returns ``(flops, bytes)`` of one decode call."""
    L, d, V = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    P = layer_params(c)
    tb = dtype_bytes(c["torch_dtype"])
    flops = 0.0
    nbytes = float(weight_bytes(c))
    n = 0
    for ctx in contexts:
        n += 1
        flops += 2.0 * P * L + L * _attn_flops(c, ctx + 1) + 2.0 * d * V
        nbytes += _kv_row_bytes(c) * (ctx + 1) + d * tb
    return (flops, nbytes) if n else (0.0, 0.0)


def roofline_s(flops: float, nbytes: float, pk: dict) -> float:
    """Least time the chip needs: the larger of compute and memory."""
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])

