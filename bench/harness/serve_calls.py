"""Roofline share of one serving program over the traced window."""
from __future__ import annotations

from typing import Optional

from bench.harness import trace as TR
from bench.harness import work as WK


def roofline(rec: dict, kind: str) -> Optional[float]:
    """Least time the chip needs for the required work of the window's
    ``kind`` calls (per chip), over the device time of the program whose
    module name holds ``kind``, in percent."""
    calls = [c for c in rec["surface"].get("calls", ()) if c["kind"] == kind]
    dev_ns, n = TR.module_ns(rec, kind)
    if not calls or not n or dev_ns <= 0:
        return None
    pk = WK.peaks(rec["device_kind"])
    chips = rec["chips"]
    bound = sum(WK.roofline_s(c["flops"] / chips, c["bytes"] / chips, pk)
                for c in calls)
    return 100.0 * bound / (dev_ns * 1e-9)
