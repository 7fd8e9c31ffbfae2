"""Engine and scheduler: wall time of each ``Engine.step`` minus the time
spent inside the executor's prefill, decode and reset calls, averaged
over the steps of the window.  Milliseconds, host clock."""


def read(rec):
    steps = rec["surface"].get("steps") or []
    if not steps:
        return None
    return sum(s["end"] - s["start"] - s["exec"] for s in steps) * 1e3 \
        / len(steps)
