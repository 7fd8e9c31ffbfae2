"""Split each timed sweep call into host prep, device time and
materialization, from its ``bench.sweep.call`` span and the device
operations inside it."""
from __future__ import annotations

from typing import List

from bench.harness import trace as TR

CALL_SPAN = "bench.sweep.call"


def split(rec: dict) -> List[dict]:
    """Per traced call: ``prep_ns`` (span start to first device op),
    ``device_ns`` (device busy inside the span, averaged over chips),
    ``post_ns`` (last device op to span end), with the call's
    ``scenarios`` and ``packets``.  Calls without a device op are left
    out."""
    calls = rec["surface"].get("calls", [])
    spans = TR.spans(rec, CALL_SPAN)
    out = []
    for (a, b), call in zip(spans, calls):
        ext = TR.device_extent(rec, a, b)
        if ext is None:
            continue
        out.append({"prep_ns": ext[0] - a, "post_ns": b - ext[1],
                    "device_ns": TR.busy_ns(rec, a, b),
                    "scenarios": call["scenarios"],
                    "packets": call["packets"]})
    return out
