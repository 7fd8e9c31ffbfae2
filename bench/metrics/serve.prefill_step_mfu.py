"""Whole-step model FLOP/s utilization of the steps on the first-token
path: the FLOPs that the tokens of every engine step that ran a prefill
call require (its prefill and its decode, as in the rooflines), over
the summed wall time of those steps, times the chips and their bf16
peak.  Percent."""
from bench.harness import work as WK


def read(rec):
    s = rec["surface"]
    calls = s.get("calls", ())
    wall = flops = 0.0
    for st in s.get("steps", ()):
        inside = [c for c in calls if st["start"] <= c["start"] <= st["end"]]
        if any(c["kind"] == "prefill" for c in inside):
            wall += st["end"] - st["start"]
            flops += sum(c["flops"] for c in inside)
    if wall <= 0 or flops <= 0:
        return None
    pk = WK.peaks(rec["device_kind"])
    return 100.0 * flops / (wall * rec["chips"] * pk["bf16_flops_per_s"])
