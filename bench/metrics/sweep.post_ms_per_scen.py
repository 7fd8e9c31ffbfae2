"""Materialization per scenario: from the last device operation of each
timed ``run_sweep_specs`` call to its return (device-to-host copy and
the per-replica result objects).  Milliseconds per scenario."""
from bench.harness.sweep_calls import split


def read(rec):
    parts = split(rec)
    if not parts:
        return None
    return sum(p["post_ns"] for p in parts) * 1e-6 / sum(p["scenarios"]
                                                         for p in parts)
