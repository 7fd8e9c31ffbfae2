"""Sharding rules: parameter / input / activation PartitionSpecs.

Mesh axes (launch/mesh.py):
  * ``data``  — DP batch axis; doubles as the FSDP/ZeRO-3 axis in training.
  * ``model`` — TP/EP axis (heads, d_ff hidden, vocab, experts).
  * ``pod``   — optional leading multi-pod axis: extra DP (default) or the
    pipeline axis (distributed/pipeline.py).

Rules are *logical*: each parameter leaf is matched by the suffix of its
tree path to a template over trailing dims; leading dims added by
scan-over-layers stacking are padded with ``None`` automatically.  An axis
is only applied when the dim size is divisible by the mesh axis size —
non-divisible cases (e.g. whisper's 20 heads over model=16) degrade to
replication of that dim instead of relying on GSPMD padding, keeping
memory analysis exact.

Serving mode drops the FSDP ``data`` axis from weights (pure TP — weights
replicated across DP so decode never all-gathers them) unless the config
opts in via ``serve_keep_fsdp`` (llama4-400B cannot fit TP-only).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeSpec

# Logical axis names used in rule templates.
FSDP = "fsdp"      # -> "data" (train) / dropped (serve, unless keep_fsdp)
TP = "tp"          # -> "model"
EP = "ep"          # -> "model" (experts); "data" when serve_keep_fsdp moe
DP = "dp"          # -> ("pod", "data") batch sharding


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Resolved mapping logical axis -> mesh axis (or None)."""
    fsdp: Optional[str] = "data"
    tp: Optional[str] = "model"
    ep: Optional[str] = "model"

    def resolve(self, logical: Optional[str]) -> Optional[str]:
        if logical is None:
            return None
        return {FSDP: self.fsdp, TP: self.tp, EP: self.ep}[logical]


TRAIN_RULES = ShardingRules(fsdp="data", tp="model", ep="model")
SERVE_RULES = ShardingRules(fsdp=None, tp="model", ep="model")
# llama4-400B serving: experts sharded over data, expert hidden over model.
SERVE_FSDP_RULES = ShardingRules(fsdp=None, tp="model", ep="data")


# ---------------------------------------------------------------------------
# rule table: ordered (path-regex, template-over-trailing-dims)
# ---------------------------------------------------------------------------
# The regex is matched against "/"-joined tree paths like
# "groups/0/mixer/wq" or "front/1/moe/w_down".  First match wins.
_PARAM_RULES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    # --- embeddings / head ---------------------------------------------------
    # embed d-dim deliberately UNSHARDED: an FSDP 'data' entry there makes
    # the lookup-gather output d-sharded over the batch axis, and SPMD
    # resolves the conflict by replicating the activations — silently
    # 16x-ing all downstream compute (EXPERIMENTS.md §Perf iteration 1).
    (r"(^|/)embed$",                 (TP, None)),     # (vocab, d)
    (r"(^|/)lm_head$",               (None, TP)),     # (d, vocab)
    # --- MoE (before generic mlp names; expert weights are rank-3) ----------
    (r"moe/router$",                 (FSDP, None)),   # (d, E)
    (r"moe/shared/w_(gate|up)$",     (FSDP, TP)),
    (r"moe/shared/w_down$",          (TP, FSDP)),
    (r"moe/w_(gate|up)$",            (EP, FSDP, TP)),  # (E, d, f)
    (r"moe/w_down$",                 (EP, TP, FSDP)),  # (E, f, d)
    # --- MLA -----------------------------------------------------------------
    (r"mixer/w_dkv$",                (FSDP, None)),   # (d, rank+rope)
    (r"mixer/w_u[kv]$",              (None, TP)),     # (rank, H*hd)
    (r"mixer/kv_norm$",              (None,)),
    # --- attention (also matches encdec "cross/") ----------------------------
    (r"(mixer|cross)/w[qkv]$",       (FSDP, TP)),     # (d, proj)
    (r"(mixer|cross)/wo$",           (TP, FSDP)),     # (proj, d)
    (r"mixer/b[qkv]$",               (TP,)),
    (r"mixer/[qk]_norm$",            (None,)),
    # --- SSD (mamba2) ---------------------------------------------------------
    (r"mixer/w_[zx]$",               (FSDP, TP)),     # (d, d_in)
    (r"mixer/w_[BC]$",               (FSDP, None)),   # (d, G*N) small
    (r"mixer/w_dt$",                 (FSDP, TP)),     # (d, H)
    (r"mixer/conv_x_w$",             (None, TP)),
    (r"mixer/conv_x_b$",             (TP,)),
    (r"mixer/conv_[BC]_[wb]$",       (None, None)),   # trailing dims padded
    (r"mixer/(A_log|D|dt_bias)$",    (TP,)),
    (r"mixer/gate_norm$",            (TP,)),
    (r"mixer/out_proj$",             (TP, FSDP)),     # (d_in, d)
    # --- RG-LRU ----------------------------------------------------------------
    (r"mixer/w_gate$",               (FSDP, TP)),     # (d, w)
    (r"mixer/w_x$",                  (FSDP, TP)),
    (r"mixer/conv_w$",               (None, TP)),
    (r"mixer/conv_b$",               (TP,)),
    (r"mixer/(lambda_|[ai]_gate_[wb])$", (TP,)),
    (r"mixer/w_out$",                (TP, FSDP)),     # (w, d)
    # --- dense MLP --------------------------------------------------------------
    (r"mlp/w_(gate|up)$",            (FSDP, TP)),     # (d, f)
    (r"mlp/w_down$",                 (TP, FSDP)),     # (f, d)
    # --- norms & everything small ------------------------------------------------
    (r"norm",                        (None,)),
    (r".",                           ()),             # default: replicate
)


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


def _axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):
        n = 1
        for a in name:
            n *= mesh.shape.get(a, 1)
        return n
    return mesh.shape.get(name, 1)


def _spec_for_leaf(path_s: str, shape: Tuple[int, ...], mesh: Mesh,
                   rules: ShardingRules) -> P:
    for pat, template in _PARAM_RULES:
        if re.search(pat, path_s):
            tmpl = template
            break
    else:  # pragma: no cover — final rule always matches
        tmpl = ()
    ndim = len(shape)
    k = min(len(tmpl), ndim)
    trailing = tmpl[len(tmpl) - k:] if k else ()
    spec: list = [None] * (ndim - k)
    used: set = set()
    for dim_size, logical in zip(shape[ndim - k:], trailing):
        axis = rules.resolve(logical)
        members = (set(axis) if isinstance(axis, tuple)
                   else {axis} if axis else set())
        # first-wins dedup: a mesh axis shards at most one dim (e.g. MoE
        # (E,d,f) in train: EP takes 'model', so TP on f degrades to None)
        if axis is not None and not (members & used) \
                and dim_size % _axis_size(mesh, axis) == 0 \
                and _axis_size(mesh, axis) > 1:
            # drop tuple components absent from this mesh
            if isinstance(axis, tuple):
                axis = tuple(a for a in axis if mesh.shape.get(a, 1) > 1)
                axis = axis if len(axis) > 1 else (axis[0] if axis else None)
            spec.append(axis)
            used |= members
        else:
            spec.append(None)
    # embed fallback: vocab not divisible by the TP axis (mamba2 50280,
    # whisper 51866) -> keep the table fully replicated.  (Sharding d over
    # 'model' instead trips an XLA SPMD partitioner bug when the grad-
    # accumulation scan dynamic-slices the gathered embeddings; the
    # replicated table costs ~0.2 GB/device for these vocabs.)
    return P(*spec)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
def rules_for(cfg: ModelConfig, mode: str,
              mesh: Optional[Mesh] = None) -> ShardingRules:
    if mode == "train":
        # multipod: FSDP spans (pod, data) so 400B-class params/grads
        # shard over every DP chip, not just within one pod
        if mesh is not None and mesh.shape.get("pod", 1) > 1:
            return ShardingRules(fsdp=("pod", "data"), tp="model",
                                 ep="model")
        return TRAIN_RULES
    if cfg.serve_keep_fsdp:
        return SERVE_FSDP_RULES
    return SERVE_RULES


def param_pspecs(cfg: ModelConfig, params: Any, mesh: Mesh,
                 mode: str = "train") -> Any:
    """Tree of PartitionSpec matching ``params`` (arrays or ShapeDtypeStruct)."""
    rules = rules_for(cfg, mode, mesh)

    def leaf(path, x):
        return _spec_for_leaf(_path_str(path), tuple(x.shape), mesh, rules)

    return jax.tree_util.tree_map_with_path(leaf, params)


def batch_axes(mesh: Mesh, n: Optional[int] = None):
    """Dim-0 spec entry for batch sharding: 'data', ('pod','data'), or None.

    If ``n`` is given, the largest divisible prefix of the DP axes is used
    (e.g. batch=128 on (pod=2, data=16): 'data' only would be dropped too;
    we try ('pod','data'), then 'data', then 'pod', then None).
    """
    cands = [("pod", "data"), ("data",), ("pod",)]
    for c in cands:
        axes = tuple(a for a in c if _axis_size(mesh, a) > 1)
        if not axes:
            continue
        if n is None or n % _prod(mesh, axes) == 0:
            return axes if len(axes) > 1 else axes[0]
    return None


def _prod(mesh: Mesh, axes) -> int:
    total = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        total *= _axis_size(mesh, a)
    return total


def batch_pspec(mesh: Mesh, n: Optional[int] = None) -> P:
    return P(batch_axes(mesh, n))


def input_pspecs(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh,
                 kind: Optional[str] = None) -> Dict[str, P]:
    """PartitionSpecs for the batch dict fed to train/serve steps."""
    kind = kind or shape.kind
    entry = batch_axes(mesh, shape.global_batch)
    specs: Dict[str, P] = {}
    names = ("tokens", "labels") if kind == "train" else ("tokens",)
    for n in names:
        specs[n] = P(entry, None)
    if cfg.frontend_stub and kind == "train":
        specs["vis_embeds"] = P(entry, None, None)
        specs["vis_mask"] = P(entry, None)
    if cfg.is_encoder_decoder and kind == "train":
        specs["frames"] = P(entry, None, None)
    return specs


# ---------------------------------------------------------------------------
# activation constraints (set once by the trainer / serve builder): SPMD
# sharding propagation through while-loop (scan) carries is unreliable —
# without an in-body anchor the batch sharding dissolves and XLA replicates
# the whole layer stack (EXPERIMENTS.md §Perf iteration 1).
# ---------------------------------------------------------------------------
_ACT_MESH: Optional[Mesh] = None
_ACT_SEQ_AXIS: Optional[str] = None


def set_activation_mesh(mesh: Optional[Mesh],
                        seq_axis: Optional[str] = None) -> None:
    """seq_axis='model' enables sequence parallelism: the residual stream
    is anchored (B, S/model, d) between blocks, so GSPMD replaces the TP
    all-reduces with reduce-scatter + all-gather pairs and S-shards the
    norm/residual memory (EXPERIMENTS.md §Perf iteration 7)."""
    global _ACT_MESH, _ACT_SEQ_AXIS
    _ACT_MESH = mesh
    _ACT_SEQ_AXIS = seq_axis


def constrain_acts(x: jnp.ndarray) -> jnp.ndarray:
    """Anchor (B, S, ...) activations to batch-over-DP inside scan bodies."""
    if _ACT_MESH is None:
        return x
    spec = [batch_axes(_ACT_MESH, x.shape[0])] + [None] * (x.ndim - 1)
    if _ACT_SEQ_AXIS is not None and x.ndim == 3 and x.shape[1] > 1:
        spec[1] = _ACT_SEQ_AXIS
    return constrain(x, _ACT_MESH, *spec)


def constrain(x: jnp.ndarray, mesh: Mesh, *spec) -> jnp.ndarray:
    """with_sharding_constraint that silently drops unknown/undivisible axes."""
    fixed = []
    for dim, s in zip(x.shape, spec):
        if s is None:
            fixed.append(None)
            continue
        axes = s if isinstance(s, tuple) else (s,)
        axes = tuple(a for a in axes if _axis_size(mesh, a) > 1)
        if axes and dim % _prod(mesh, axes) == 0:
            fixed.append(axes if len(axes) > 1 else axes[0])
        else:
            fixed.append(None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*fixed)))


def logical_to_pspec(template, shape, mesh, rules: ShardingRules) -> P:
    spec = []
    for dim, logical in zip(shape, template):
        axis = rules.resolve(logical)
        if axis and dim % _axis_size(mesh, axis) == 0 \
                and _axis_size(mesh, axis) > 1:
            spec.append(axis)
        else:
            spec.append(None)
    return P(*spec)


def shardings_for(tree_of_specs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_of_specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# cache sharding (serving): batch over data; kv-heads/length placement
# ---------------------------------------------------------------------------
def cache_pspecs(cfg: ModelConfig, cache: Any, mesh: Mesh,
                 shard_length: bool = False) -> Any:
    """KV-cache placement.

    Default: batch(slot) dim over ``data``; kv-heads over ``model`` when
    divisible, else the *length* dim over ``model`` (GQA kv=8 on a 16-way
    TP axis — e.g. qwen3/llama4 decode — shards the 32k context instead).
    ``shard_length`` (long_500k, batch=1): length over ``data`` too.
    The positions (``pos``) take the length placement of their layer's
    keys and values: whole on each ``model`` shard when the heads are split
    there, so the in-place writes and the masks built from them need no
    exchange.

    Cache leaves are (B,H,T,D) k/v/xk/xv, (B,T,r) ckv/krope, (B,T) pos,
    (B,W-1,C) conv, (B,H,P,N) ssd state, (B,W) rglru h — possibly under
    leading scan-stack dims; the trailing structure is keyed by leaf name.
    """
    data = "data" if _axis_size(mesh, "data") > 1 else None
    model = "model" if _axis_size(mesh, "model") > 1 else None
    heads_on_model = (model is not None and cfg.mla is None
                      and cfg.num_kv_heads % _axis_size(mesh, model) == 0)

    def leaf(path, x):
        p = _path_str(path)
        last = p.rsplit("/", 1)[-1]
        shape = tuple(x.shape)
        nd = len(shape)
        spec: list = [None] * nd
        tdim = hdim = None
        if last == "pos":
            bdim = nd - 2
            tdim = nd - 1
        elif last.startswith("conv"):
            bdim = nd - 3
        elif last == "state":
            bdim = nd - 4
            hdim = nd - 3
        elif last in ("ckv", "krope"):
            bdim = nd - 3
            tdim = nd - 2
        elif last == "h":
            bdim = nd - 2
        else:  # k / v / xk / xv, head-major
            bdim = nd - 4
            hdim = nd - 3
            tdim = nd - 2
        bdim = max(bdim, 0)

        def fits(dim, axis):
            return (dim is not None and axis is not None
                    and shape[dim] % _axis_size(mesh, axis) == 0)

        if not shard_length and fits(bdim, data):
            spec[bdim] = data
        elif shard_length and fits(tdim, data):
            spec[tdim] = data
        if fits(hdim, model):
            spec[hdim] = model
        elif (tdim is not None and spec[tdim] is None and fits(tdim, model)
              and not (last == "pos" and heads_on_model)):
            spec[tdim] = model
        return P(*spec)

    return jax.tree_util.tree_map_with_path(leaf, cache)
