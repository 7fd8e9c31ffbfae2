"""WLBVT eligibility+select round as a Pallas TPU kernel (DESIGN.md §13.3).

One *dispatch round* of the simulator grants up to ``free_pus`` PU slots:
each pick recomputes eligibility (queue non-empty, occupancy under the
weighted ``pu_limit`` cap) and takes the eligible tenant with the lowest
priority-normalized throughput.  Within a round the throughput metric is
constant — picks move packets from queue to PU, touching only
``queue_len``/``cur_occup`` — so the metric is hoisted and each iteration
is a masked min over the ``[R, T]`` tenant lanes of a replica sweep.

Three value-identical implementations, selected ``attn_impl``-style:

* ``jnp``     — ``lax.while_loop`` with whole-batch early exit: a lane
  that returns -1 can never pick again this round (its state did not
  change), so once every lane stalls the remaining iterations are
  provably all -1 and are skipped.  Default on CPU; used by the device
  datapath inside its ``lax.scan`` step.
* ``jnp_ref`` — dense ``lax.scan`` over all ``max_picks`` iterations.
  The documented reference the Pallas kernel must match bit-exactly.
* ``pallas``  — TPU kernel: 8-row grid blocks over replicas, tenant
  lanes padded to the 128-wide VPU register; ``fori_loop`` over picks
  with the first-argmin computed by the min-index trick (min over lane
  iota where the metric equals its row min — identical tie-break to
  ``argmin``).  f32 lanes; on CPU it runs in the Pallas interpreter.

All three share the formulas in ``core/sched_generic`` (``pu_limit`` /
``select`` are the single source of truth); the equality is pinned by
tests/test_devicepath.py.

Contract: ``prio/total_occup/bvt`` float ``[R, T]``, ``queue_len``/
``cur_occup`` int32 ``[R, T]``, ``free_k`` int32 ``[R]`` (PUs grantable
per replica).  Returns ``(picks [R, max_picks] int32 (-1 = no grant,
trailing -1 padded), queue_len', cur_occup')``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core import sched_generic as G

_BR = 8       # replica rows per grid block (fp32 sublane tile)
_LANES = 128  # tenant lanes per block (VPU register width)


def _one_pick(k, prio, queue_len, cur_occup, total_occup, bvt, metric,
              free_k, num_pus: int):
    """One masked pick across all replica lanes; -1 where nothing is
    eligible or the replica's grantable-PU budget ``free_k`` is spent."""
    idx = G.select_lanes(prio, queue_len, cur_occup, total_occup, bvt,
                         num_pus, jnp, metric=metric)
    can = (idx >= 0) & (k < free_k)
    iv = jnp.where(can, idx, 0)
    lane = lax.broadcasted_iota(jnp.int32, queue_len.shape,
                                queue_len.ndim - 1)
    hot = (lane == iv[..., None]) & can[..., None]
    queue_len = queue_len - hot.astype(queue_len.dtype)
    cur_occup = cur_occup + hot.astype(cur_occup.dtype)
    pick = jnp.where(can, idx, -1).astype(jnp.int32)
    return pick, queue_len, cur_occup


def _rounds_jnp(prio, queue_len, cur_occup, total_occup, bvt, free_k, *,
                num_pus: int, max_picks: int):
    """Early-exit round driver (value-identical to the dense reference)."""
    metric = G.tput(total_occup, bvt, jnp) / prio
    R = queue_len.shape[0]
    if max_picks == 1:     # single-grant fast path: no loop machinery
        pick, ql, co = _one_pick(jnp.int32(0), prio, queue_len, cur_occup,
                                 total_occup, bvt, metric, free_k, num_pus)
        return pick[:, None], ql, co
    picks0 = jnp.full((R, max_picks), -1, jnp.int32)

    def cond(st):
        k, _ql, _co, _picks, alive = st
        return (k < max_picks) & alive

    def body(st):
        k, ql, co, picks, _alive = st
        pick, ql, co = _one_pick(k, prio, ql, co, total_occup, bvt,
                                 metric, free_k, num_pus)
        picks = picks.at[:, k].set(pick)
        return k + 1, ql, co, picks, jnp.any(pick >= 0)

    st = (jnp.int32(0), queue_len, cur_occup, picks0, jnp.asarray(True))
    _, ql, co, picks, _ = lax.while_loop(cond, body, st)
    return picks, ql, co


def wlbvt_select_rounds_ref(prio, queue_len, cur_occup, total_occup, bvt,
                            free_k, *, num_pus: int, max_picks: int):
    """Dense ``lax.scan`` reference — the Pallas kernel's bit-exact
    oracle (tests/test_devicepath.py)."""
    metric = G.tput(total_occup, bvt, jnp) / prio

    def step(carry, k):
        ql, co = carry
        pick, ql, co = _one_pick(k, prio, ql, co, total_occup, bvt,
                                 metric, free_k, num_pus)
        return (ql, co), pick

    ks = jnp.arange(max_picks, dtype=jnp.int32)
    (ql, co), picks = lax.scan(step, (queue_len, cur_occup), ks)
    return jnp.moveaxis(picks, 0, -1), ql, co


# ---------------------------------------------------------------------------
# pallas
# ---------------------------------------------------------------------------
def _select_kernel(prio_ref, ql_ref, co_ref, to_ref, bvt_ref, free_ref,
                   picks_ref, qlo_ref, coo_ref, *, num_pus: int,
                   max_picks: int):
    prio = prio_ref[...]                       # (BR, LANES) float
    to = to_ref[...]
    bvt = bvt_ref[...]
    fk = free_ref[...][:, :1]                  # (BR, 1) int32
    lane = lax.broadcasted_iota(jnp.int32, prio.shape, 1)
    # hoisted: constant within a round (picks touch only ql/co)
    metric0 = (to / jnp.maximum(bvt, 1.0)) / prio

    def body(k, st):
        ql, co, picks = st
        nonempty = ql > 0
        psum = jnp.sum(jnp.where(nonempty, prio, 0.0), axis=1,
                       keepdims=True)
        lim = jnp.ceil(num_pus * prio / jnp.maximum(psum, 1e-9)
                       - G.CEIL_EPS)
        lim = jnp.where(psum > 0, lim, float(num_pus))
        elig = nonempty & (co.astype(prio.dtype) < lim)
        masked = jnp.where(elig, metric0, G.BIG)
        m = jnp.min(masked, axis=1, keepdims=True)
        # first-argmin: min lane index among the row minima
        idx = jnp.min(jnp.where(masked == m, lane, _LANES), axis=1,
                      keepdims=True)
        can = jnp.any(elig, axis=1, keepdims=True) & (k < fk)
        hot = (lane == idx) & can
        ql = ql - hot.astype(ql.dtype)
        co = co + hot.astype(co.dtype)
        picks = jnp.where(lane == k, jnp.where(can, idx, -1), picks)
        return ql, co, picks

    picks0 = jnp.full(prio.shape, -1, jnp.int32)
    ql, co, picks = lax.fori_loop(
        0, max_picks, body, (ql_ref[...], co_ref[...], picks0))
    picks_ref[...] = picks
    qlo_ref[...] = ql
    coo_ref[...] = co


def _rounds_pallas(prio, queue_len, cur_occup, total_occup, bvt, free_k, *,
                   num_pus: int, max_picks: int, interpret: bool = False):
    R, T = prio.shape
    if T > _LANES or max_picks > _LANES:
        raise ValueError(
            f"pallas wlbvt_select supports T<= {_LANES} tenants and "
            f"max_picks <= {_LANES} (got T={T}, max_picks={max_picks})")
    pad_r = (-R) % _BR
    pad_t = _LANES - T
    Rp = R + pad_r
    # pad lanes are inert: queue_len 0 => never nonempty, never eligible
    prio_p = jnp.pad(prio, ((0, pad_r), (0, pad_t)), constant_values=1.0)
    ql_p = jnp.pad(queue_len, ((0, pad_r), (0, pad_t)))
    co_p = jnp.pad(cur_occup, ((0, pad_r), (0, pad_t)))
    to_p = jnp.pad(total_occup, ((0, pad_r), (0, pad_t)))
    bvt_p = jnp.pad(bvt, ((0, pad_r), (0, pad_t)))
    free_p = jnp.broadcast_to(
        jnp.pad(free_k.astype(jnp.int32), (0, pad_r))[:, None],
        (Rp, _LANES))
    kernel = functools.partial(_select_kernel, num_pus=num_pus,
                               max_picks=max_picks)
    spec = pl.BlockSpec((_BR, _LANES), lambda i: (i, 0))
    picks, ql, co = pl.pallas_call(
        kernel,
        grid=(Rp // _BR,),
        in_specs=[spec] * 6,
        out_specs=[spec] * 3,
        out_shape=[
            jax.ShapeDtypeStruct((Rp, _LANES), jnp.int32),
            jax.ShapeDtypeStruct((Rp, _LANES), queue_len.dtype),
            jax.ShapeDtypeStruct((Rp, _LANES), cur_occup.dtype),
        ],
        interpret=interpret,
        name="wlbvt_select",
    )(prio_p, ql_p, co_p, to_p, bvt_p, free_p)
    return picks[:R, :max_picks], ql[:R, :T], co[:R, :T]


def wlbvt_select_rounds(prio, queue_len, cur_occup, total_occup, bvt,
                        free_k, *, num_pus: int, max_picks: int,
                        impl: str = "", interpret: bool = False):
    """Backend switch (``attn_impl`` idiom): '' picks pallas on TPU with
    f32 lanes and the early-exit jnp path otherwise (the TPU kernel
    compiler has no 64-bit types).  Interpret mode is a CPU-only
    decision; on any other backend the kernel is compiled."""
    backend = jax.default_backend()
    if not impl:
        impl = ("pallas" if backend == "tpu" and prio.dtype == jnp.float32
                else "jnp")
    if impl == "jnp":
        return _rounds_jnp(prio, queue_len, cur_occup, total_occup, bvt,
                           free_k, num_pus=num_pus, max_picks=max_picks)
    if impl == "jnp_ref":
        return wlbvt_select_rounds_ref(prio, queue_len, cur_occup,
                                       total_occup, bvt, free_k,
                                       num_pus=num_pus,
                                       max_picks=max_picks)
    if impl == "pallas":
        interpret = interpret or backend == "cpu"
        if not interpret and prio.dtype == jnp.float64:
            raise ValueError(
                "pallas wlbvt_select compiles f32 lanes only (the TPU "
                "kernel compiler has no 64-bit types); use impl='jnp' for "
                "f64 lanes (precision='exact') or precision='fast'")
        return _rounds_pallas(prio, queue_len, cur_occup, total_occup, bvt,
                              free_k, num_pus=num_pus, max_picks=max_picks,
                              interpret=interpret)
    raise ValueError(f"unknown wlbvt_select impl {impl!r} "
                     "(expected jnp | jnp_ref | pallas)")
