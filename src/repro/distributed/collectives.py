"""Overlap-friendly collectives (shard_map level).

``collective_matmul_ag`` implements the all-gather <-> matmul overlap
("collective matmul", Wang et al.): instead of all-gathering the
row-sharded LHS and then multiplying, each step multiplies the locally
resident shard while ``ppermute`` rotates the next shard around the ring —
compute hides the ICI transfer.  Used by the beyond-paper perf path for
FSDP weight gathering (EXPERIMENTS.md §Perf) and exercised by tests on a
host-device mesh.

``reduce_scatter_matmul`` is the mirrored pattern for the output
projection: psum_scatter interleaved with the per-shard matmuls.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh


def _ring_perm(n: int, shift: int = 1):
    return [(i, (i + shift) % n) for i in range(n)]


def collective_matmul_ag(x: jnp.ndarray, w_shard: jnp.ndarray,
                         axis_name: str) -> jnp.ndarray:
    """Computes ``x @ all_gather(w_shard, axis)`` with compute/comm overlap.

    Inside shard_map: ``w_shard`` is this device's (d_in/n, d_out) slice of
    a row-sharded weight; x is (..., d_in) fully replicated along
    ``axis_name``.  Each iteration multiplies the currently-held shard
    against the matching x columns while rotating shards ring-wise.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    blk = w_shard.shape[0]

    def step(i, carry):
        acc, w_cur = carry
        # perm (r -> r+1): after i rotations this rank holds the shard that
        # originated at rank (idx - i) mod n
        src = (idx - i) % n
        x_blk = jax.lax.dynamic_slice_in_dim(x, src * blk, blk, axis=-1)
        acc = acc + x_blk @ w_cur
        w_nxt = jax.lax.ppermute(w_cur, axis_name, _ring_perm(n))
        return acc, w_nxt

    out_shape = x.shape[:-1] + (w_shard.shape[1],)
    acc0 = jnp.zeros(out_shape, w_shard.dtype)
    # unrolled fori so ppermute of the last iteration is dead-code-eliminated
    acc, w = acc0, w_shard
    for i in range(n - 1):
        acc, w = step(i, (acc, w))
    src = (idx - (n - 1)) % n
    x_blk = jax.lax.dynamic_slice_in_dim(x, src * blk, blk, axis=-1)
    return acc + x_blk @ w


def reduce_scatter_matmul(x_shard: jnp.ndarray, w_shard: jnp.ndarray,
                          axis_name: str) -> jnp.ndarray:
    """Row-parallel matmul with ring reduce-scatter overlap.

    x_shard: (..., d_in/n) — the contraction dim is sharded; w_shard:
    (d_in/n, d_out) matching rows.  Each rank's ``x_shard @ w_shard`` is a
    full-width partial sum; instead of an all-reduce, the partials are
    ring-reduce-scattered so each rank ends with its fully-reduced
    (..., d_out/n) column slot — and each matmul chunk overlaps with the
    neighbour transfer.  Equivalent to psum_scatter(x @ w) over the last
    dim.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    part = x_shard @ w_shard                             # (..., d_out)
    d_out = part.shape[-1]
    blk = d_out // n

    def chunk(j):
        return jax.lax.dynamic_slice_in_dim(part, j * blk, blk, axis=-1)

    if n == 1:
        return part
    # ring reduce-scatter (perm r -> r+1): rank q initiates the buffer for
    # slot (q-1); a buffer reaching rank r at step s was initiated by rank
    # (r-s) for slot (r-s-1), so rank r adds chunk((r-s-1) % n).  After
    # n-1 steps rank r holds the fully-reduced chunk r.
    buf = chunk((idx - 1) % n)
    for s in range(1, n):
        buf = jax.lax.ppermute(buf, axis_name, _ring_perm(n))
        buf = buf + chunk((idx - s - 1) % n)
    return buf


def all_gather_interleaved(shard: jnp.ndarray, axis_name: str,
                           tile_fn) -> jnp.ndarray:
    """Generic overlap driver: applies ``tile_fn(i, shard_i)`` as shards
    arrive ring-wise and sums the results."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    out = tile_fn((idx + 0) % n, shard)
    cur = shard
    for i in range(1, n):
        cur = jax.lax.ppermute(cur, axis_name, _ring_perm(n))
        out = out + tile_fn((idx + i) % n, cur)
    return out


def psum_pods_then_data(x: jnp.ndarray, mesh: Mesh) -> jnp.ndarray:
    """Hierarchical gradient all-reduce: reduce within the pod first (fast
    ICI), then across pods (slow DCN/ICI link) — one value crosses the pod
    boundary per element instead of the full DP fan-in."""
    if "data" in mesh.shape:
        x = jax.lax.psum(x, "data")
    if "pod" in mesh.shape:
        x = jax.lax.psum(x, "pod")
    return x
