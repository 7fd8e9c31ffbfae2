"""Whole-step model FLOP/s utilization: the FLOPs the tokens of the
window require (prefill and decode, as in the rooflines), over the
summed wall time of the engine steps that ran a program, times the
chips and their bf16 peak.  Percent."""
from bench.harness import work as WK


def read(rec):
    s = rec["surface"]
    wall = sum(st["end"] - st["start"] for st in s.get("steps", ())
               if st["exec"] > 0)
    flops = sum(c["flops"] for c in s.get("calls", ()))
    if wall <= 0 or flops <= 0:
        return None
    pk = WK.peaks(rec["device_kind"])
    return 100.0 * flops / (wall * rec["chips"] * pk["bf16_flops_per_s"])
