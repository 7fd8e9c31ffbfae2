"""The one generator that turns a traffic mix file into inputs.

A mix file is data: ``bench/traffic/<mix>.json``.  Two kinds exist:

* sweep mixes: a tenant table (listed, or ``tenant_count`` tenants that
  cycle through ``tenant_classes``), the scenario's duration, horizon,
  FIFO capacity and scheduler, and ``replicas`` per launch;
* serving mixes: open-loop arrivals at ``rate_per_s`` over tenants with
  lognormal prompt and output lengths.

Every seed gets the same work: the request count is fixed by the rate
and the window, each tenant's count by its share, lengths are the
quantiles of their distributions and gaps the quantiles of the
exponential, put in one order by the mix's own ``schedule_seed``.  The
run's seed draws the token ids (and the surface's weights).  When the
run's seed also chose the order, the tokens served in a 50 s window
swung by 15% from seed to seed against 2% between two runs of one seed
(PERF.md): which long request lands near the window's end decides what
finishes inside it.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


# ---------------------------------------------------------------------------
# sweep mixes
# ---------------------------------------------------------------------------
def sweep_tenants(mix: dict) -> List[dict]:
    """The mix's tenant table, one dict per tenant with ``name``,
    ``pkt_bytes``, ``share``, ``seed_offset``, ``priority``,
    ``compute_base``, ``compute_per_byte``, ``spin_factor``,
    ``kernel_cycle_limit`` and ``total_cycle_limit``."""
    if "tenants" in mix:
        return [dict(t) for t in mix["tenants"]]
    n = int(mix["tenant_count"])
    cycle = mix["tenant_cycle"]
    out = []
    for i in range(n):
        cls = cycle[i % len(cycle)]
        out.append({**mix["tenant_classes"][cls], "name": f"{cls}{i}",
                    "share": 1.0 / n, "seed_offset": i})
    return out


def sweep_scenario(mix: dict) -> dict:
    """What one replica simulates, as plain data."""
    return {"tenants": sweep_tenants(mix), "scheduler": mix["scheduler"],
            "duration_us": float(mix["duration_us"]),
            "horizon_us": float(mix.get("horizon_us", 0.0)),
            "fifo_capacity": int(mix["fifo_capacity"])}


def replica_seeds(mix: dict, seed: int) -> List[int]:
    """The seed block of one run: replica r of ``--seed n`` is seed
    ``R * n + r``."""
    R = int(mix["replicas"])
    return [R * int(seed) + r for r in range(R)]


# ---------------------------------------------------------------------------
# serving mixes
# ---------------------------------------------------------------------------
def _lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    nd = NormalDist()
    q = [(k + 0.5) / n for k in range(n)]
    vals = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(u))
            for u in q]
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def _split(n: int, shares: List[float]) -> List[int]:
    """Largest-remainder split of ``n`` by ``shares``."""
    raw = [n * s / sum(shares) for s in shares]
    out = [int(math.floor(r)) for r in raw]
    order = sorted(range(len(raw)), key=lambda i: -(raw[i] - out[i]))
    for i in order[:n - sum(out)]:
        out[i] += 1
    return out


def serve_requests(mix: dict, seed: int, seconds: float,
                   vocab: int) -> List[Dict]:
    """Open-loop schedule: ``[{"t", "tenant", "prompt", "max_new"}]`` in
    arrival order, ``t`` in seconds from the window's start.  The order
    of tenants, lengths and gaps comes from the mix's ``schedule_seed``,
    so every run replays one arrival trace; ``seed`` draws the prompts'
    token ids."""
    rng = np.random.default_rng(int(mix["schedule_seed"]))
    tok_rng = np.random.default_rng(seed)
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    tens = mix["tenants"]
    counts = _split(n, [t["share"] for t in tens])
    labels = np.concatenate([np.full(c, i, np.int64)
                             for i, c in enumerate(counts)])
    rng.shuffle(labels)
    lengths = {}
    for i, (t, c) in enumerate(zip(tens, counts)):
        p = _lognormal_quantiles(t["prompt"], c)
        o = _lognormal_quantiles(t["output"], c)
        rng.shuffle(p)
        rng.shuffle(o)
        o = np.minimum(o, int(mix["max_total_tokens"]) - p)
        lengths[i] = list(zip(p.tolist(), o.tolist()))
    gaps = np.array([-math.log(1.0 - (k + 0.5) / n) / rate for k in range(n)])
    rng.shuffle(gaps)
    times = np.cumsum(gaps) - gaps[0]
    out = []
    for t, lab in zip(times, labels):
        p, o = lengths[int(lab)].pop()
        out.append({"t": float(t), "tenant": int(lab),
                    "prompt": tok_rng.integers(1, vocab, size=p,
                                               dtype=np.int64)
                    .astype(np.int32),
                    "max_new": int(o)})
    return out
