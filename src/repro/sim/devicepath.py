"""Device-resident sim datapath: jit/scan event loop, vmapped over an
R-replica sweep axis (DESIGN.md §13).

The host datapaths (``sim/engine.py`` event loop, ``sim/fastpath.py``
SoA batch) interpret one scenario at a time in Python.  This module
compiles the *whole* inner loop — arrival ingestion, FMQ push with ECN
mark-before-drop, WLBVT/RR dispatch rounds, budget-clamp kills,
completion bookkeeping, occupancy/BVT folds, EQ emission — as a single
``lax.scan`` over a fixed event-step grid, ``jax.vmap``-ed over replica
lanes, so a whole ``SweepSpec`` runs in one device launch.

Event model (per replica, fixed shapes): the heap of the host loop
degenerates, on the compute-only contract below, to a two-way merge of
the (pre-sorted) arrival array against the PU slot table's min
finish-time.  Arrival seqs are assigned at inject (0..n-1) and
completion seqs start at n, so an arrival always precedes a completion
at equal time and completion ties resolve by lower seq — exactly the
host heap's ``(time, seq)`` order.  Each scan step consumes at most one
event; dead steps (replica drained or past horizon) are masked no-ops,
so ragged replicas ride the same grid.

Device contract — ``device_eligible`` returns the reason a spec needs
the host path: compute-only workloads (``io_kind == "none"``; the
DWRR/AXI/egress machinery never engages), no QoS controller (windows
then carry no decisions, only telemetry flushes), wlbvt/rr scheduling,
no timeline/trace capture.  Inside the contract the device path is
decision/EQ/telemetry **bit-identical** to the host datapaths under
``precision="exact"`` (f64 via a scoped ``enable_x64``); the only
documented drift is the Jain time-average, whose host fold compresses
the active set before summing (DESIGN.md §8).  ``precision="fast"``
trades f64 for f32 lanes (TPU-native, Pallas-eligible) and downgrades
the parity claim to statistical.

The WLBVT eligibility+select round itself lives in
``repro.kernels.wlbvt_select`` (jnp reference + Pallas TPU kernel
behind an ``attn_impl``-style switch).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.osmosis_pspin import PSPIN
from repro.core import sched_generic as G
from repro.core.events import Event, EventKind
from repro.kernels.wlbvt_select import wlbvt_select_rounds
from repro.telemetry.wallclock import span

EQ_RING_CAPACITY = 4096   # host EQHub shared-queue retention

# ys codes -> EQ event kinds (0 = no event this step)
_EQ_KINDS = {
    1: EventKind.ECN_MARK,
    2: EventKind.QUEUE_OVERFLOW,
    3: EventKind.CYCLE_BUDGET_EXCEEDED,
    4: EventKind.TOTAL_BUDGET_EXCEEDED,
}


class DevicePathError(ValueError):
    """Spec falls outside the device-path contract."""


def device_eligible(spec) -> Optional[str]:
    """None when ``spec`` fits the device contract, else the reason it
    must run on a host datapath."""
    if getattr(spec, "analytic", ""):
        return "analytic scenario (no datapath at all)"
    if getattr(spec, "num_nics", 0):
        return "fleet spec (switch fabric is host-only)"
    if spec.controller is not None:
        return "QoS controller (host-only control plane)"
    if spec.scheduler not in ("wlbvt", "rr"):
        return f"scheduler {spec.scheduler!r} (device supports wlbvt|rr)"
    if spec.record_timeline:
        return "record_timeline (host-only window capture)"
    for t in spec.tenants:
        wl = t.workload.build()
        if wl.io_kind != "none":
            return (f"tenant {t.name!r} io_kind {wl.io_kind!r} "
                    "(DWRR IO path is host-only)")
    return None


# ---------------------------------------------------------------------------
# traced step (factory-closed over static geometry; jit root = _launch)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=64)
def _build_launch(T: int, P: int, C: int, S: int, scheduler: str,
                  impl: str):
    """One compiled launch per (tenants, PUs, ring, steps, sched, impl)
    geometry.  Returns ``jit(_launch)(state, data) -> (state, ys)``.

    Single-grant theorem (what makes the step cheap): the host dispatch
    loop maintains the quiescence invariant "free_pus == 0 or nothing
    eligible" after every event.  An arrival adds exactly one packet (a
    new non-empty queue only *shrinks* other tenants' ``pu_limit``), a
    completion frees exactly one PU — so every event grants **at most
    one** PU under both wlbvt and rr, and the per-event dispatch is a
    single masked select + branchless apply, no loop.  (The round-level
    ``max_picks > 1`` generality lives in ``wlbvt_select_rounds`` and
    is pinned against ``core.sched_generic.select_round`` in tests.)

    Slot arrays are sized ``P + 1``: index P is an inert pad (t_fin
    ``+inf``, seq sentinel) that masked scatters aim at, so no
    gather-merge is needed on the no-op branch.  Likewise the FIFO ring
    is ``C + 1`` wide with column C as the discard target.
    """
    dma_ns = PSPIN.cycles_ns(PSPIN.dma_setup_cycles)
    ns_per_cycle = PSPIN.ns_per_cycle
    wlbvt = scheduler == "wlbvt"
    sent = np.iinfo(np.int32).max

    def _pre(s, d):
        """Consume one event (or nothing): pick the earliest of the next
        arrival and the earliest slot finish, advance the BVT/Jain
        integrals to it, apply the event, emit the EQ/completion record."""
        na = s["na"]
        ta = d["arr_t"][na]
        tfin = s["s_tf"][:, 0]           # slot pairs: (t_fin, t0)
        tmin = jnp.min(tfin)
        # completion candidate: lowest seq among the min-finish slots
        pc = jnp.argmin(jnp.where(tfin == tmin, s["s_ps"][:, 1], sent))
        is_arr = ta <= tmin            # arrival seqs < completion seqs
        t_ev = jnp.where(is_arr, ta, tmin)
        live = (t_ev <= d["horizon"]) & (t_ev < jnp.inf)
        t = jnp.where(live, t_ev, s["now"])
        prio = d["prio"]
        fdt = prio.dtype
        # --- advance fold (Simulator._advance_to, pre-event state) ----
        dt0 = t - s["last_adv"]
        dt = jnp.where(live & (dt0 > 0.0), dt0, 0.0)
        ql = s["queue_len"]
        co = s["cur_occup"]
        act = (ql > 0) | (co > 0)
        occf = co.astype(fdt)
        to = s["total_occup"] + jnp.where(act, occf * dt, 0.0)
        bvt = s["bvt"] + jnp.where(act, dt, 0.0)
        x = jnp.where(act, occf / prio, 0.0)
        actn, s1, s2 = jnp.sum(                 # one fused reduction
            jnp.stack([act.astype(fdt), x, x * x]), axis=-1)
        jain = jnp.where(s2 > 0.0, s1 * s1 / (actn * s2), 1.0)
        two_act = actn >= 2.0
        jain_acc = s["jain_acc"] + jnp.where(two_act, jain * dt, 0.0)
        jain_t = s["jain_t"] + jnp.where(two_act, dt, 0.0)
        # --- arrival branch (FMQ push: admit -> overflow -> ECN) ------
        ia = d["arr_tenant"][na]
        qa = ql[ia]
        marr = live & is_arr
        full = qa >= d["fifo_cap"]
        acc = marr & (~full)
        drop = marr & full
        mark = acc & ((qa + 1) >= d["ecn_thresh"])
        # --- completion branch (slot meta packed: pkt | kill<<30 |
        # budget-kill<<31; tenant is derivable from the packet id) ------
        mcomp = live & (~is_arr)
        pk = s["s_ps"][pc, 0]            # slot pairs: (pkt-meta, seq)
        jc = pk & jnp.int32((1 << 30) - 1)
        ic = d["arr_tenant"][jc]
        kflag = (((pk >> 30) & 1) != 0) & mcomp
        bkflag = (((pk >> 31) & 1) != 0) & mcomp
        one = jnp.int32(1)
        zero = jnp.int32(0)
        # --- apply (masked scatters aim at the pad slot/column) -------
        ql = ql.at[ia].add(jnp.where(acc, one, zero))
        co = co.at[ic].add(jnp.where(mcomp, -one, zero))
        tail = jnp.mod(s["fifo_head"][ia] + qa, C)
        tail_w = jnp.where(acc, tail, C)
        buf = s["fifo_buf"].at[ia, tail_w].set(na)
        # the freed slot keeps its stale seq: seqs are only consulted
        # among the tfin == tmin slots, and a freed slot sits at +inf
        # until the next grant overwrites both fields
        pc_w = jnp.where(mcomp, pc, P)
        tf2 = s["s_tf"].at[pc_w, 0].set(jnp.inf)
        free = s["free_pus"] + jnp.where(mcomp, one, zero)
        # --- per-step records (step order IS host heap-pop order, so
        # the completion stream needs no carried per-packet arrays; the
        # packed slot meta ships as-is, -1 = no completion) -------------
        # host op order: now - (t0 - dma_ns), NOT now - grant
        ktime = t - (s["s_tf"][pc, 1] - dma_ns)
        comp_meta = jnp.where(mcomp, pk, jnp.int32(-1))
        # --- EQ (at most one event per step; code | tenant<<3 packed) -
        eq_code = jnp.where(drop, jnp.int32(2), jnp.where(mark, one, zero))
        eq_code = jnp.where(
            kflag, jnp.where(bkflag, jnp.int32(4), jnp.int32(3)), eq_code)
        eq_pack = eq_code | (jnp.where(is_arr, ia, ic).astype(jnp.int32)
                             << 3)
        s = {
            **s,
            "na": na + jnp.where(marr, one, zero),
            "now": jnp.where(live, t, s["now"]),
            "last_adv": jnp.where(live, t, s["last_adv"]),
            "queue_len": ql, "cur_occup": co,
            "total_occup": to, "bvt": bvt,
            "fifo_buf": buf, "s_tf": tf2,
            "free_pus": free, "jain_acc": jain_acc, "jain_t": jain_t,
        }
        aux = {
            "t": t,
            "free_k": jnp.where(live, free, zero),
            "eq_pack": eq_pack, "comp_meta": comp_meta,
            "comp_ktime": jnp.where(mcomp, ktime, 0.0),
        }
        return s, aux

    def _rr_pick(ptr, ql, co, free_k):
        """Host `_dispatch` rr arm, single-grant form: the pointer only
        advances on an actual grant (host never probes with 0 free)."""
        idx, ptr1 = G.select_rr(ptr, ql, jnp)
        can = (idx >= 0) & (free_k > 0)
        iv = jnp.where(can, idx, 0)
        lane = lax.broadcasted_iota(jnp.int32, ql.shape, 0)
        hot = (lane == iv) & can
        ql = ql - hot.astype(ql.dtype)
        co = co + hot.astype(co.dtype)
        ptr = jnp.where(can, ptr1, ptr).astype(jnp.int32)
        pick = jnp.where(can, idx, -1).astype(jnp.int32)
        return pick, ptr, ql, co

    def _apply_one(s, d, pick, t):
        """Host ``_pop_and_start`` for the (single) winner: FIFO pop,
        budget clamps (exact op order of the inlined BudgetLedger
        mirror), slot fill, ``(t_fin, seq)`` heap push."""
        won = pick >= 0
        i = jnp.where(won, pick, 0)
        head_i = s["fifo_head"][i]
        j = s["fifo_buf"][i, jnp.mod(head_i, C)]
        head = s["fifo_head"].at[i].add(jnp.where(won, jnp.int32(1),
                                                  jnp.int32(0)))
        comp = d["arr_comp"][j]
        lm = d["lims"][i]                 # (klim, tlim) in one gather
        klim = lm[0]
        kill1 = (klim > 0) & (comp > klim)
        comp = jnp.where(kill1, klim, comp)
        tlim = lm[1]
        remaining = tlim - s["spent"][i]
        bk = (tlim > 0) & (comp > remaining)
        comp = jnp.where(bk, jnp.where(remaining > 0.0, remaining, 0.0),
                         comp)
        spent = s["spent"].at[i].add(jnp.where(won, comp, 0.0))
        slot = jnp.argmax(s["s_tf"][:, 0] == jnp.inf)  # any free slot:
        sw = jnp.where(won, slot, P)                   # order (t_fin, seq)
        t0v = t + dma_ns
        tfv = t0v + comp * ns_per_cycle
        meta = (j | ((kill1 | bk).astype(jnp.int32) << 30)
                | (bk.astype(jnp.int32) << 31))
        return {
            **s,
            "fifo_head": head, "spent": spent,
            "s_tf": s["s_tf"].at[sw].set(
                jnp.stack([jnp.where(won, tfv, jnp.inf), t0v])),
            "s_ps": s["s_ps"].at[sw].set(
                jnp.stack([meta, jnp.where(won, s["seq"], sent)])),
            "seq": s["seq"] + jnp.where(won, jnp.int32(1), jnp.int32(0)),
            "free_pus": s["free_pus"] - jnp.where(won, jnp.int32(1),
                                                  jnp.int32(0)),
        }

    def _step(st, data):
        st, aux = jax.vmap(_pre)(st, data)
        if wlbvt:
            picks, ql2, co2 = wlbvt_select_rounds(
                data["prio"], st["queue_len"], st["cur_occup"],
                st["total_occup"], st["bvt"], aux["free_k"],
                num_pus=P, max_picks=1, impl=impl)
            pick = picks[:, 0]
            st = {**st, "queue_len": ql2, "cur_occup": co2}
        else:
            pick, ptr2, ql2, co2 = jax.vmap(_rr_pick)(
                st["rr_ptr"], st["queue_len"], st["cur_occup"],
                aux["free_k"])
            st = {**st, "rr_ptr": ptr2, "queue_len": ql2, "cur_occup": co2}
        st = jax.vmap(_apply_one)(st, data, pick, aux["t"])
        return st, (aux["eq_pack"], aux["t"], aux["comp_meta"],
                    aux["comp_ktime"])

    def _launch(state, data):
        def body(st, _):
            return _step(st, data)
        return lax.scan(body, state, None, length=S)

    return jax.jit(_launch)


# ---------------------------------------------------------------------------
# host side: spec -> replica arrays -> launch -> results
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class DeviceRunResult:
    """Per-replica result with the host ``SimResult`` observables the
    device contract covers (stats are real ``TenantStats``; EQ events
    carry the host ring's last-4096 retention)."""
    spec: object
    time: float
    stats: Dict[int, "object"]
    jain_pu_timeavg: float
    jain_io_timeavg: float
    events: List[Event]
    events_dropped: int
    completions: List[Tuple[int, float]]
    counters: Dict[str, np.ndarray]
    sched_state: dict

    def throughput_gbps(self, tenant: int) -> float:
        st = self.stats[tenant]
        return st.served_payload_bytes * 8.0 / max(self.time, 1e-9)

    def summary_row(self, knobs: Optional[dict] = None) -> dict:
        """Flat JSON-portable sweep report row (RunReport-style)."""
        row = {
            "scenario": self.spec.name,
            "seed": self.spec.seed,
            "knobs": dict(knobs or {}),
            "time_ns": self.time,
            "jain_pu_timeavg": self.jain_pu_timeavg,
            "events": len(self.events),
            "tenants": [],
        }
        for i, t in enumerate(self.spec.tenants):
            st = self.stats[i]
            row["tenants"].append({
                "name": t.name,
                "completed": st.completed,
                "killed": st.killed,
                "drops": st.drops,
                "ecn_marks": int(self.counters["ecn_marks"][i]),
                "throughput_gbps": self.throughput_gbps(i),
                "p50_kernel_ns": st.kernel_time_percentile(50),
                "p99_kernel_ns": st.kernel_time_percentile(99),
            })
        return row


def _spec_arrays(spec, ftype) -> dict:
    """Replica-local host arrays for one spec (trace + per-tenant
    config), with the exact float ops ``BatchedSimulator._inject``
    applies (payload clamp, compute-cycles formula)."""
    from repro.api.runtime import build_traces
    ta = build_traces(spec, arrays=True)
    tn = ta.tenants.astype(np.int64)
    sz = ta.sizes.astype(np.int64)
    payload = np.maximum(0, sz - PSPIN.header_bytes)
    wls = [t.workload.build() for t in spec.tenants]
    spin = np.array([w.spin_factor for w in wls])
    base = np.array([w.compute_base for w in wls])
    cpb = np.array([w.compute_per_byte for w in wls])
    comp = spin[tn] * (base[tn] + cpb[tn] * payload)
    cap = int(spec.fifo_capacity)
    thresh = max(1, (3 * cap) // 4)                          # FMQ default
    horizon = spec.horizon_us * 1e3 if spec.horizon_us else np.inf
    return {
        "n": len(ta),
        "n_live": int(np.sum(ta.times <= horizon)),
        "arr_t": ta.times.astype(np.float64),
        "arr_tenant": tn.astype(np.int32),
        "arr_size": sz.astype(ftype),
        "arr_payload": payload.astype(ftype),
        "arr_comp": comp.astype(ftype),
        "prio": np.array([t.priority for t in spec.tenants], ftype),
        "fifo_cap": np.int32(cap),
        "ecn_thresh": np.int32(thresh),
        "klim": np.array([float(t.kernel_cycle_limit)
                          for t in spec.tenants], ftype),
        "tlim": np.array([float(t.total_cycle_limit)
                          for t in spec.tenants], ftype),
        "horizon": ftype(horizon),
    }


def _stack_data(per_spec: List[dict], ftype) -> Tuple[dict, np.ndarray, int]:
    """Pad ragged replica arrays to a common grid; index NB is the inert
    sentinel row (arrival at +inf / zero-size packet).  Only what the
    traced step reads ships to the device — sizes/payloads stay host-side
    and the counters are reconstructed from the EQ/completion streams."""
    R = len(per_spec)
    NB = max(a["n"] for a in per_spec)
    arr_t = np.full((R, NB + 1), np.inf, np.float64)
    arr_tenant = np.zeros((R, NB + 1), np.int32)
    arr_comp = np.zeros((R, NB + 1), ftype)
    n_arr = np.zeros(R, np.int32)
    for r, a in enumerate(per_spec):
        n = a["n"]
        n_arr[r] = n
        arr_t[r, :n] = a["arr_t"]
        arr_tenant[r, :n] = a["arr_tenant"]
        arr_comp[r, :n] = a["arr_comp"]
    data = {
        "arr_t": arr_t.astype(ftype),
        "arr_tenant": arr_tenant,
        "arr_comp": arr_comp,
        "prio": np.stack([a["prio"] for a in per_spec]),
        "fifo_cap": np.array([a["fifo_cap"] for a in per_spec], np.int32),
        "ecn_thresh": np.array([a["ecn_thresh"] for a in per_spec],
                               np.int32),
        "lims": np.stack([np.stack([a["klim"], a["tlim"]], axis=-1)
                          for a in per_spec]),
        "horizon": np.array([a["horizon"] for a in per_spec], ftype),
    }
    return data, n_arr, NB


def _init_state(R: int, T: int, P: int, C: int, NB: int, n_arr,
                ftype) -> dict:
    """Slot arrays carry an inert pad at index P and the FIFO ring a
    discard column at index C (masked scatters aim there, see
    ``_build_launch``); no per-tenant counters ride the carry — they are
    all recoverable from the EQ/completion streams in ``_materialize``."""
    i32 = np.int32
    return {
        "now": np.zeros(R, ftype),
        "last_adv": np.zeros(R, ftype),
        "na": np.zeros(R, i32),
        "seq": n_arr.astype(i32),          # completion seqs start at n
        "free_pus": np.full(R, P, i32),
        "rr_ptr": np.zeros(R, i32),
        "queue_len": np.zeros((R, T), i32),
        "cur_occup": np.zeros((R, T), i32),
        "total_occup": np.zeros((R, T), ftype),
        "bvt": np.zeros((R, T), ftype),
        "fifo_head": np.zeros((R, T), i32),
        "fifo_buf": np.zeros((R, T, C + 1), i32),
        "spent": np.zeros((R, T), ftype),
        # slot pairs: s_tf = (t_fin, t0) float, s_ps = (pkt-meta, seq)
        # int32 — paired so grant/free are single row scatters
        "s_tf": np.stack([np.full((R, P + 1), np.inf, ftype),
                          np.zeros((R, P + 1), ftype)], axis=-1),
        "s_ps": np.stack([np.full((R, P + 1), NB, i32),
                          np.full((R, P + 1), np.iinfo(np.int32).max,
                                  i32)], axis=-1),
        "jain_acc": np.zeros(R, ftype),
        "jain_t": np.zeros(R, ftype),
    }


def _materialize(spec, a: dict, fin_state, ys, r: int,
                 record_completions: bool) -> DeviceRunResult:
    """Rebuild the host-side result objects for replica ``r`` (``a`` is
    the replica's ``_spec_arrays`` dict)."""
    from repro.sim.engine import TenantStats
    T = len(spec.tenants)
    g = {k: np.asarray(v[r]) for k, v in fin_state.items()}
    (eq_pack, eq_t, comp_meta, comp_ktime) = (np.asarray(y[:, r])
                                              for y in ys)
    eq_code = eq_pack & 7
    eq_ten = eq_pack >> 3
    time = float(g["now"])
    # step order IS the host heap-pop (t_fin, seq) order
    steps = np.flatnonzero(comp_meta != -1)
    meta = comp_meta[steps]
    arr_tenant = a["arr_tenant"].astype(np.int64)
    arr_t = a["arr_t"]
    na = int(g["na"])
    fin = eq_t[steps]
    ktimes = comp_ktime[steps]
    killed = ((meta >> 30) & 1) != 0        # pkt | kill<<30 | bk<<31
    pkts = (meta & ((1 << 30) - 1)).astype(np.int64)
    ten_of = arr_tenant[pkts]
    if record_completions:
        completions = [(int(i), float(t))
                       for i, t in zip(ten_of, fin)]
    else:
        completions = []
    # counters reconstructed from the streams (nothing rides the carry):
    # arrivals/bytes from the first na trace rows, drops/marks from EQ
    # codes, completions from the (packet, killed) stream.  Byte sums are
    # nonnegative integers < 2^53, so order of summation is irrelevant.
    tb = np.arange(T + 1, dtype=np.int64)
    arrivals = np.histogram(arr_tenant[:na], bins=tb)[0]
    bytes_in = np.histogram(arr_tenant[:na], bins=tb,
                            weights=a["arr_size"][:na].astype(np.float64))[0]
    drops = np.histogram(eq_ten[eq_code == 2], bins=tb)[0]
    ecn_marks = np.histogram(eq_ten[eq_code == 1], bins=tb)[0]
    completed = np.histogram(ten_of[~killed], bins=tb)[0]
    n_killed = np.histogram(ten_of[killed], bins=tb)[0]
    payload = a["arr_payload"].astype(np.float64)
    bytes_out = np.histogram(ten_of[~killed], bins=tb,
                             weights=payload[pkts[~killed]])[0]
    counters = {
        "arrivals": arrivals,
        "drops": drops,
        "ecn_marks": ecn_marks,
        "enqueued": arrivals - drops,
        "completed": completed,
        "killed": n_killed,
        "bytes_in": bytes_in,
        "bytes_out": bytes_out,
    }
    stats: Dict[int, TenantStats] = {}
    for i in range(T):
        st = TenantStats(
            completed=int(counters["completed"][i]),
            killed=int(counters["killed"][i]),
            drops=int(counters["drops"][i]),
            served_payload_bytes=float(counters["bytes_out"][i]),
        )
        proc = arr_tenant[:na] == i
        if proc.any():
            st.first_arrival = float(arr_t[:na][proc].min())
        mine = np.flatnonzero(ten_of == i)
        if mine.size:
            st.last_completion = float(fin[mine].max())
            # completion order: exact reservoir replay, vectorized
            st.record_kernel_times(ktimes[mine])
        stats[i] = st
    live = np.flatnonzero(eq_code > 0)
    dropped = max(0, live.size - EQ_RING_CAPACITY)
    live = live[dropped:]                 # trim before materializing
    events = [Event(tenant=int(eq_ten[k]), kind=_EQ_KINDS[int(eq_code[k])],
                    time=float(eq_t[k])) for k in live]
    jt = float(g["jain_t"])
    cap = np.full(T, int(spec.fifo_capacity), np.float64)
    return DeviceRunResult(
        spec=spec,
        time=time,
        stats=stats,
        jain_pu_timeavg=float(g["jain_acc"]) / jt if jt else 1.0,
        jain_io_timeavg=1.0,
        events=events,
        events_dropped=dropped,
        completions=completions,
        counters=counters,
        sched_state={
            "prio": a["prio"].astype(np.float64),
            "total_occup": g["total_occup"].astype(np.float64),
            "bvt": g["bvt"].astype(np.float64),
            "kv_pressure": g["queue_len"].astype(np.float64) / cap,
        },
    )


def _check_batch(specs) -> str:
    """Validate a sweep batch; returns its shared scheduler."""
    for spec in specs:
        reason = device_eligible(spec)
        if reason:
            raise DevicePathError(
                f"spec {spec.name!r} needs a host datapath: {reason}")
    T = len(specs[0].tenants)
    sched = specs[0].scheduler
    for spec in specs:
        if len(spec.tenants) != T or spec.scheduler != sched:
            raise DevicePathError(
                "sweep replicas must share tenant count and scheduler "
                f"(got T={len(spec.tenants)}/{T}, "
                f"scheduler={spec.scheduler!r}/{sched!r})")
    return sched


@contextlib.contextmanager
def _precision(precision: str):
    """Yield the lane float type; ``"exact"`` scopes ``enable_x64``."""
    if precision == "exact":
        with jax.enable_x64(True):
            yield np.float64
    elif precision == "fast":
        yield np.float32
    else:
        raise ValueError(f"unknown precision {precision!r} (exact|fast)")


def run_sweep_specs(specs: Sequence, *, impl: str = "",
                    precision: str = "exact",
                    record_completions: bool = False,
                    ) -> List[DeviceRunResult]:
    """Run every spec as one replica lane of a single device launch.

    All specs must share tenant count and scheduler (one ``SweepSpec``
    expansion always does).  ``precision="exact"`` traces under a scoped
    ``enable_x64`` for bit-exact f64 parity with the host datapaths;
    ``"fast"`` uses f32 lanes (TPU-native).  ``record_completions``
    materializes the per-packet completion list (parity tests); sweeps
    keep it off — the summary rows never read it.
    """
    if not specs:
        return []
    specs = list(specs)
    sched = _check_batch(specs)
    R = len(specs)
    with _precision(precision) as ftype:
        geom, state, data, per_spec = _prepare_batch(specs, ftype, sched,
                                                     impl)
        with span("osmosis.sweep.launch", replicas=R):
            fin_state, eq = _build_launch(*geom)(state, data)
        with span("osmosis.sweep.fetch", replicas=R):
            fin_state = jax.tree_util.tree_map(np.asarray, fin_state)
            eq = jax.tree_util.tree_map(np.asarray, eq)
    with span("osmosis.sweep.materialize", replicas=R):
        return [_materialize(s, per_spec[r], fin_state, eq, r,
                             record_completions)
                for r, s in enumerate(specs)]


def lower_sweep(specs: Sequence, *, impl: str = "",
                precision: str = "exact"):
    """``jax.stages.Lowered`` of the launch ``run_sweep_specs`` makes for
    these specs: the same jitted function on the same arguments, so
    ``.compile()`` here is the program that run executes (its text shows
    whether the Pallas kernel is in it: ``tpu_custom_call``)."""
    specs = list(specs)
    sched = _check_batch(specs)
    with _precision(precision) as ftype:
        geom, state, data, _ = _prepare_batch(specs, ftype, sched, impl)
        return _build_launch(*geom).lower(state, data)


def _prepare_batch(specs, ftype, sched: str, impl: str):
    """Host prep for one batch: the launch geometry (``_build_launch``'s
    arguments), initial state, device data and replica arrays."""
    T = len(specs[0].tenants)
    P = PSPIN.num_pus
    R = len(specs)
    with span("osmosis.sweep.build", replicas=R):
        per_spec = [_spec_arrays(s, ftype) for s in specs]
    with span("osmosis.sweep.stack", replicas=R):
        data, n_arr, NB = _stack_data(per_spec, ftype)
        if NB >= (1 << 30) - 1:   # slot meta packs pkt | kill<<30 | bk<<31
            raise DevicePathError(f"trace too long for device path ({NB})")
        C = max(1, min(int(max(s.fifo_capacity for s in specs)), NB))
        S = 2 * max(a["n_live"] for a in per_spec) + 2
        state = _init_state(R, T, P, C, NB, n_arr, ftype)
    return (T, P, C, S, sched, impl), state, data, per_spec


# ---------------------------------------------------------------------------
# the oracle: the same spec on the host batched datapath
# ---------------------------------------------------------------------------
PARITY_STAT_FIELDS = ("completed", "killed", "drops", "served_payload_bytes",
                      "first_arrival", "last_completion",
                      "kernel_time_count", "kernel_time_sum")


def host_oracle(spec, *, record_completions: bool = True):
    """The device path's reference: ``spec`` on the host batched
    datapath (``sim/fastpath.py``, f64)."""
    from repro.api.runtime import build_traces
    from repro.core.slo import ECTX
    from repro.sim.fastpath import build_simulator
    tenants = [ECTX(tenant_id=i, name=t.name, slo=t.slo(),
                    kernel=t.workload.build())
               for i, t in enumerate(spec.tenants)]
    sim = build_simulator(tenants, datapath="batched",
                          scheduler=spec.scheduler, frag=spec.frag(),
                          arb=spec.arbiter,
                          fifo_capacity=spec.fifo_capacity,
                          record_completions=record_completions)
    ta = build_traces(spec, arrays=True)
    horizon = spec.horizon_us * 1e3 if spec.horizon_us else None
    return sim.run(ta, horizon=horizon)


def parity_mismatches(spec, host, dev) -> List[str]:
    """Names of the observables on which a device result ``dev`` is not
    bit-identical to the ``host_oracle`` result (empty = exact parity).
    The Jain time-average is the one documented drift (the host fold
    compresses the active set before summing): it may differ by 1e-9."""
    bad = []
    if dev.time != host.time:
        bad.append("time")
    if dev.completions != host.completions:
        bad.append("completions")
    if ([(e.tenant, e.kind, e.time) for e in dev.events]
            != [(e.tenant, e.kind, e.time) for e in host.events]):
        bad.append("events")
    for i in range(len(spec.tenants)):
        hs, ds = host.stats[i], dev.stats[i]
        for f in PARITY_STAT_FIELDS:
            if getattr(ds, f) != getattr(hs, f):
                bad.append(f"tenant{i}.{f}")
        if ds.kernel_time_percentile(99) != hs.kernel_time_percentile(99):
            bad.append(f"tenant{i}.p99_kernel_ns")
    for k in ("prio", "total_occup", "bvt", "kv_pressure"):
        if not np.array_equal(np.asarray(dev.sched_state[k]),
                              np.asarray(host.sched_state[k])):
            bad.append(f"sched_state.{k}")
    if not abs(dev.jain_pu_timeavg - host.jain_pu_timeavg) <= 1e-9:
        bad.append("jain_pu_timeavg")
    return bad


def run_device(spec, *, impl: str = "",
               precision: str = "exact",
               record_completions: bool = True) -> DeviceRunResult:
    """Single-scenario convenience wrapper (R=1 sweep)."""
    return run_sweep_specs([spec], impl=impl, precision=precision,
                           record_completions=record_completions)[0]
