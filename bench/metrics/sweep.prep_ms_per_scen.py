"""Host prep per scenario: from the start of each timed
``run_sweep_specs`` call (its ``bench.sweep.call`` span) to the first
device operation inside it: trace build, array stacking, transfer and
launch.  Milliseconds per scenario, over the traced window."""
from bench.harness.sweep_calls import split


def read(rec):
    parts = split(rec)
    if not parts:
        return None
    return sum(p["prep_ns"] for p in parts) * 1e-6 / sum(p["scenarios"]
                                                         for p in parts)
