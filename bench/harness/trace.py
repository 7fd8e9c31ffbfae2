"""Profiler trace -> the record that per-layer readers take apart.

The traced run wraps its window in ``jax.profiler`` and places host
spans (``jax.profiler.TraceAnnotation``, names starting ``bench.``)
around each call into a layer.  ``load`` turns the ``.xplane.pb`` file
into plain Python: host spans and, per device, the intervals of its XLA
modules (whole programs) and XLA ops, all on the profiler's one clock.
The reductions below work on that form only, so they are tested on
small hand-made records.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# an XLA op's event name is its whole HLO instruction (a `while` lists
# every loop-carried shape); the breakdown keeps the head of it
NAME_CHARS = 160

Interval = Tuple[float, float]


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(path: str) -> dict:
    """``{"spans": [(name, start, end)], "devices": [{"id", "ops":
    (names, starts, ends), "modules": (names, starts, ends)}]}``, times in
    ns.  Host spans are the ``bench.`` annotations only."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans: List[Tuple[str, float, float]] = []
    devices = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"id": int(m.group(1)), "ops": _empty(), "modules": _empty()}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = _columns(line.events)
                elif line.name == MODULES_LINE:
                    dev["modules"] = _columns(line.events)
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    spans.sort(key=lambda s: s[1])
    devices.sort(key=lambda d: d["id"])
    return {"spans": spans, "devices": devices}


def _empty():
    return ([], np.zeros(0), np.zeros(0))


def _columns(events):
    names, starts, durs = [], [], []
    for e in events:
        names.append(e.name)
        starts.append(e.start_ns)
        durs.append(e.duration_ns)
    s = np.asarray(starts, np.float64)
    return names, s, s + np.asarray(durs, np.float64)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------
def merge(starts: np.ndarray, ends: np.ndarray, lo: float = -np.inf,
          hi: float = np.inf) -> List[Interval]:
    """Union of the intervals, clipped to ``[lo, hi]``, as sorted
    disjoint ``(start, end)`` pairs."""
    s = np.clip(np.asarray(starts, np.float64), lo, hi)
    e = np.clip(np.asarray(ends, np.float64), lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    order = np.argsort(s, kind="stable")
    out: List[Interval] = []
    for a, b in zip(s[order], e[order]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((float(a), float(b)))
    return out


def busy_intervals(dev: dict, lo: float = -np.inf,
                   hi: float = np.inf) -> List[Interval]:
    """When an operation ran on this device: the union of its XLA op
    events, or of its module events where the trace has no op line."""
    names, s, e = dev["ops"]
    if not len(names):
        names, s, e = dev["modules"]
    return merge(s, e, lo, hi)


def length(iv: Sequence[Interval]) -> float:
    return float(sum(b - a for a, b in iv))


def window(rec: dict) -> Interval:
    for name, a, b in rec["spans"]:
        if name == WINDOW_SPAN:
            return a, b
    raise ValueError("trace has no bench.window span")


def busy_ns(rec: dict, lo: Optional[float] = None,
            hi: Optional[float] = None) -> float:
    """Device busy time in ``[lo, hi]`` (default: the window), averaged
    over the chips in the trace."""
    if lo is None or hi is None:
        lo, hi = window(rec)
    devs = rec["devices"]
    if not devs:
        return 0.0
    return sum(length(busy_intervals(d, lo, hi)) for d in devs) / len(devs)


def module_ns(rec: dict, pattern: str, lo: Optional[float] = None,
              hi: Optional[float] = None) -> Tuple[float, int]:
    """Summed device time and count of the module executions whose name
    contains ``pattern`` and that start in ``[lo, hi]``, averaged over
    the chips."""
    if lo is None or hi is None:
        lo, hi = window(rec)
    devs = rec["devices"]
    if not devs:
        return 0.0, 0
    tot, cnt = 0.0, 0
    for d in devs:
        names, s, e = d["modules"]
        for n, a, b in zip(names, s, e):
            if pattern in n and lo <= a <= hi:
                tot += b - a
                cnt += 1
    return tot / len(devs), cnt // len(devs)


def spans(rec: dict, name: str) -> List[Interval]:
    return [(a, b) for n, a, b in rec["spans"] if n == name]


def device_extent(rec: dict, lo: float, hi: float) -> Optional[Interval]:
    """First start and last end of device activity inside ``[lo, hi]``
    on any chip (None: no device op ran there)."""
    first, last = np.inf, -np.inf
    for d in rec["devices"]:
        iv = busy_intervals(d, lo, hi)
        if iv:
            first = min(first, iv[0][0])
            last = max(last, iv[-1][1])
    if first > last:
        return None
    return first, last


def top_ops(rec: dict, k: int = 10) -> List[list]:
    """The ``k`` device operations with the most time in the window,
    ``[name, seconds]``, averaged over the chips."""
    lo, hi = window(rec)
    tot: Dict[str, float] = {}
    devs = rec["devices"]
    for d in devs:
        names, s, e = d["ops"]
        if not len(names):
            names, s, e = d["modules"]
        inside = (s >= lo) & (s <= hi)
        dur = np.minimum(e, hi) - s
        for n, keep, t in zip(names, inside, dur):
            if keep:
                tot[n] = tot.get(n, 0.0) + t
    n_dev = max(len(devs), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n[:NAME_CHARS], t / n_dev * 1e-9] for n, t in best]


def idle_gaps(rec: dict, k: int = 10) -> List[list]:
    """Idle time of the first chip in the window, by what the host was
    doing: each gap between device operations is cut at the edges of the
    ``bench.`` spans inside it, and each piece is named after the
    innermost span around it, with ``.head`` while that span has not yet
    run a device op and ``.tail`` once it has run its last.  Returns the
    ``k`` names with the most idle time, ``[name, seconds]``."""
    import bisect
    lo, hi = window(rec)
    if not rec["devices"]:
        return []
    busy = busy_intervals(rec["devices"][0], lo, hi)
    inner = sorted((s for s in rec["spans"] if s[0] != WINDOW_SPAN),
                   key=lambda s: s[1])
    starts = [s[1] for s in inner]
    tot: Dict[str, float] = {}
    prev_end, prev_start = lo, None
    for i in range(len(busy) + 1):
        a = prev_end
        b = busy[i][0] if i < len(busy) else hi
        next_start = busy[i][0] if i < len(busy) else None
        if b > a:
            j0 = max(0, bisect.bisect_right(starts, a) - 16)
            j1 = bisect.bisect_right(starts, b)
            cuts = sorted({a, b} | {x for s in inner[j0:j1]
                                    for x in s[1:] if a < x < b})
            for p, q in zip(cuts, cuts[1:]):
                span = _innermost(inner, starts, 0.5 * (p + q))
                if span is None:
                    label = "host (no span)"
                else:
                    n, sa, sb = span
                    if prev_start is None or prev_start < sa:
                        label = n + ".head"
                    elif next_start is None or next_start > sb:
                        label = n + ".tail"
                    else:
                        label = n
                tot[label] = tot.get(label, 0.0) + (q - p)
        if i < len(busy):
            prev_start, prev_end = busy[i]
    best_k = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t * 1e-9] for n, t in best_k]


def _innermost(inner, starts, t, depth: int = 16):
    """The shortest span of ``inner`` (sorted by start) around ``t``;
    spans nest only a few deep, so the last few starts before ``t``
    hold it."""
    import bisect
    j = bisect.bisect_right(starts, t)
    best = None
    for n, a, b in inner[max(0, j - depth):j]:
        if a <= t <= b and (best is None or b - a < best[2] - best[1]):
            best = (n, a, b)
    return best
