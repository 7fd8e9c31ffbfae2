"""Plain event-loop reference of one OSMOSIS/PsPIN NIC under a
compute-only tenant mix (paper §5-§7).

Semantics, one scenario at a time, in ``dtype`` (float64 by default):

* traffic (paper §7.2): tenant ``i`` sends fixed-size packets at
  ``share`` of the ingress link for the scenario's duration; gaps are
  uniform on ``[0, 2 * mean)`` with the mean matched to its byte rate,
  drawn from ``numpy.random.default_rng(seed + seed_offset + 7919 * i)``;
  the tenants' packets merge in time order (ties keep tenant order);
* each packet costs ``spin * (base + per_byte * (size - header))`` PU
  cycles; a PU grant adds the DMA set-up before the kernel starts;
* FMQ per tenant: a packet that finds the FIFO full is dropped; one
  accepted while the queue reaches 3/4 of the capacity is ECN-marked;
* events run in (time, sequence) order: arrivals carry sequence numbers
  0..n-1 and completions n, n+1, ... in grant order, so an arrival comes
  before a completion at the same time; events after the horizon (if
  any) are not run;
* after each event, free PUs are granted one at a time: WLBVT takes the
  non-empty FMQ under its weighted PU cap ``ceil(P * prio / sum of
  non-empty prios - 1e-6)`` with the lowest ``total_occup / max(bvt, 1)
  / prio``, first index on ties; RR the first non-empty queue from its
  pointer.  ``total_occup`` and ``bvt`` grow while a tenant has queued
  or running packets;
* watchdog: a kernel longer than the tenant's cycle limit is cut to it
  and killed; a kernel that would overrun its lifetime budget is cut to
  what is left and killed.

Per tenant it returns the counts, served payload bytes and the sum of
kernel times (grant to completion), and the end time.
"""
from __future__ import annotations

import heapq
import math
from collections import deque
from typing import Dict, List

import numpy as np

CEIL_EPS = 1e-6
SEED_STRIDE = 7919


def trace(nic: dict, sc: dict, seed: int):
    """Arrival times (float64), tenants and sizes, merged in time order."""
    link = float(nic["ingress_gbps"])
    dur_ns = float(sc["duration_us"]) * 1e3
    times, tenants, sizes = [], [], []
    for i, t in enumerate(sc["tenants"]):
        rng = np.random.default_rng(seed + t["seed_offset"] + SEED_STRIDE * i)
        size = int(t["pkt_bytes"])
        share = float(t["share"])
        n = max(1, int(dur_ns * link * share / (8.0 * size)))
        mean_gap = size * (8.0 / (link * share))
        gaps = rng.uniform(0.0, 2.0 * np.full(n, mean_gap))
        times.append(np.cumsum(gaps) - gaps[0])
        tenants.append(np.full(n, i, np.int64))
        sizes.append(np.full(n, size, np.int64))
    tm = np.concatenate(times)
    order = np.argsort(tm, kind="stable")
    return tm[order], np.concatenate(tenants)[order], \
        np.concatenate(sizes)[order]


def simulate(nic: dict, sc: dict, seed: int, dtype=np.float64) -> Dict:
    ft = np.dtype(dtype).type
    P = int(nic["num_pus"])
    ns_per_cycle = ft(1.0 / float(nic["clock_ghz"]))
    dma = ft(float(nic["dma_setup_cycles"]) / float(nic["clock_ghz"]))
    header = int(nic["header_bytes"])
    ten = sc["tenants"]
    T = len(ten)
    cap = int(sc["fifo_capacity"])
    thresh = max(1, (3 * cap) // 4)
    horizon = (ft(float(sc["horizon_us"]) * 1e3) if sc.get("horizon_us")
               else ft(np.inf))
    arr_t, arr_ten, arr_size = trace(nic, sc, seed)
    n = len(arr_t)
    payload = np.maximum(0, arr_size - header)
    spin = np.array([t["spin_factor"] for t in ten], np.float64)
    base = np.array([t["compute_base"] for t in ten], np.float64)
    cpb = np.array([t["compute_per_byte"] for t in ten], np.float64)
    comp_all = (spin[arr_ten] * (base[arr_ten] + cpb[arr_ten] * payload)
                ).astype(dtype)
    arr_t = arr_t.astype(dtype)
    prio = np.array([t["priority"] for t in ten], dtype)
    klim = [ft(float(t["kernel_cycle_limit"])) for t in ten]
    tlim = [ft(float(t["total_cycle_limit"])) for t in ten]
    wlbvt = sc["scheduler"] == "wlbvt"

    ql = np.zeros(T, np.int64)
    co = np.zeros(T, np.int64)
    total_occup = np.zeros(T, dtype)
    bvt = np.zeros(T, dtype)
    spent = [ft(0)] * T
    queues: List[deque] = [deque() for _ in range(T)]
    heap: list = []
    seq = n
    free = P
    rr_ptr = 0
    now = last_adv = ft(0)
    out = {k: np.zeros(T, np.int64) for k in
           ("arrivals", "completed", "killed", "drops", "ecn_marks")}
    served = np.zeros(T, np.float64)
    ksum = np.zeros(T, np.float64)
    na = 0

    def pick() -> int:
        nonempty = ql > 0
        if not nonempty.any():
            return -1
        if not wlbvt:
            for k in range(T):
                i = (rr_ptr + k) % T
                if ql[i] > 0:
                    return i
            return -1
        psum = ft(prio[nonempty].sum(dtype=dtype))
        lim = np.ceil(ft(P) * prio / psum - ft(CEIL_EPS))
        elig = nonempty & (co.astype(dtype) < lim)
        if not elig.any():
            return -1
        metric = (total_occup / np.maximum(bvt, ft(1))) / prio
        return int(np.argmin(np.where(elig, metric, np.inf)))

    while True:
        ta = arr_t[na] if na < n else ft(np.inf)
        tmin = heap[0][0] if heap else ft(np.inf)
        is_arr = ta <= tmin
        t = ta if is_arr else tmin
        if not (t <= horizon and math.isfinite(float(t))):
            break
        dt = ft(t - last_adv)
        if dt > 0:
            act = (ql > 0) | (co > 0)
            total_occup = np.where(act, total_occup + co.astype(dtype) * dt,
                                   total_occup).astype(dtype)
            bvt = np.where(act, bvt + dt, bvt).astype(dtype)
        now = last_adv = t
        if is_arr:
            i = int(arr_ten[na])
            out["arrivals"][i] += 1
            if ql[i] >= cap:
                out["drops"][i] += 1
            else:
                queues[i].append(na)
                ql[i] += 1
                if ql[i] >= thresh:
                    out["ecn_marks"][i] += 1
            na += 1
        else:
            _, _, j, t0, killed = heapq.heappop(heap)
            i = int(arr_ten[j])
            co[i] -= 1
            free += 1
            ksum[i] += float(ft(t - ft(t0 - dma)))
            if killed:
                out["killed"][i] += 1
            else:
                out["completed"][i] += 1
                served[i] += float(payload[j])
        while free > 0:
            i = pick()
            if i < 0:
                break
            if not wlbvt:
                rr_ptr = (i + 1) % T
            j = queues[i].popleft()
            ql[i] -= 1
            co[i] += 1
            free -= 1
            comp = comp_all[j]
            kill = klim[i] > 0 and comp > klim[i]
            if kill:
                comp = klim[i]
            remaining = ft(tlim[i] - spent[i])
            if tlim[i] > 0 and comp > remaining:
                kill = True
                comp = remaining if remaining > 0 else ft(0)
            spent[i] = ft(spent[i] + comp)
            t0 = ft(t + dma)
            heapq.heappush(heap, (ft(t0 + comp * ns_per_cycle), seq, j, t0,
                                  kill))
            seq += 1
    return {"time": float(now), **out, "served_payload_bytes": served,
            "kernel_time_sum": ksum}
