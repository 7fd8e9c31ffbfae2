"""Device time per simulated packet: the union of the device operations
inside each timed ``run_sweep_specs`` call, over the packets those calls
simulated.  It does not depend on how the scan step or the WLBVT select
is implemented."""
from bench.harness.sweep_calls import split


def read(rec):
    parts = split(rec)
    pk = sum(p["packets"] for p in parts)
    if not parts or not pk:
        return None
    return sum(p["device_ns"] for p in parts) / pk
