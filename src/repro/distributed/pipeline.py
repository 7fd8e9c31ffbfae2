"""GPipe-style pipeline parallelism over the ``pod`` mesh axis.

The multi-pod default is DP over ``pod`` (gradient all-reduce is infrequent
and overlappable).  For models whose weights do not fit one pod, or when
the inter-pod link is too slow for per-step DP all-reduce, the ``pod`` axis
can instead carry pipeline stages: layers are partitioned into
``num_stages`` contiguous chunks and microbatches stream through with the
standard GPipe schedule (fill, steady state, drain) implemented as a
shard_map over ``pod`` with ppermute stage-to-stage handoff.

This module is deliberately self-contained: it pipelines any per-stage
``apply_fn(stage_params, x) -> x`` and is exercised by
tests/test_pipeline.py on a host-device mesh against the sequential
reference.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def stage_params(params_stacked: Any, num_stages: int) -> Any:
    """Split layer-stacked params (leading dim = layers) into per-stage
    stacks with leading dim = layers_per_stage, stacked on a new stage axis
    (so the ``pod`` axis shards stage dim 0)."""
    def split(x):
        L = x.shape[0]
        assert L % num_stages == 0, (L, num_stages)
        return x.reshape(num_stages, L // num_stages, *x.shape[1:])
    return jax.tree.map(split, params_stacked)


def gpipe(apply_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
          mesh: Mesh, axis: str = "pod"):
    """Returns pipelined(params_staged, x_microbatches) running under
    shard_map over ``axis``.

    x_microbatches: (M, mb, ...) microbatch-major input.  Each device holds
    the stage of ``params_staged`` matching its ``axis`` index.  The GPipe
    schedule runs M + S - 1 ticks; tick t processes microbatch (t - stage)
    on each stage, with ppermute handoff between ticks.  Bubble fraction =
    (S-1)/(M+S-1), reported by ``bubble_fraction``.
    """
    S = mesh.shape[axis]

    def _stage_fn(params_s, xs):
        # params_s: this device's (1, Lps, ...) stage stack; xs: (M, mb, ...)
        params_local = jax.tree.map(lambda p: p[0], params_s)
        stage = jax.lax.axis_index(axis)
        M = xs.shape[0]
        ticks = M + S - 1
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            inflight, outputs = carry
            # stage 0 injects microbatch t (if in range); others use handoff
            mb_idx = jnp.clip(t, 0, M - 1)
            inject = xs[mb_idx]
            x_in = jnp.where(stage == 0, inject, inflight)
            y = apply_fn(params_local, x_in)
            # last stage records its finished microbatch (t - (S-1))
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            valid = (t >= S - 1)
            outputs = jax.lax.cond(
                valid,
                lambda o: o.at[out_idx].set(
                    jnp.where(stage == S - 1, y, o[out_idx])),
                lambda o: o, outputs)
            # hand y to the next stage (ring; stage S-1 -> 0 is ignored)
            nxt = jax.lax.ppermute(y, axis, perm)
            return (nxt, outputs), None

        out0 = jnp.zeros_like(xs)
        inflight0 = jnp.zeros_like(xs[0])
        (_, outputs), _ = jax.lax.scan(
            tick, (inflight0, out0), jnp.arange(ticks))
        # only stage S-1 holds real outputs; broadcast via masked psum
        # (ppermute cannot multicast one source to every destination)
        if S > 1:
            outputs = jax.lax.psum(
                jnp.where(stage == S - 1, outputs,
                          jnp.zeros_like(outputs)), axis)
        return outputs

    pipelined = shard_map(
        _stage_fn, mesh=mesh,
        in_specs=(P(axis), P()),     # stage stacks sharded; x replicated
        out_specs=P(),
        check_vma=False)
    return pipelined


def bubble_fraction(num_microbatches: int, num_stages: int) -> float:
    return (num_stages - 1) / (num_microbatches + num_stages - 1)
