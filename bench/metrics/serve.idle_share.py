"""Share of the traced window in which no operation ran on the device
(1 - busy / window), in percent."""
from bench.harness import trace as TR


def read(rec):
    lo, hi = TR.window(rec)
    if not rec["devices"] or hi <= lo:
        return None
    return 100.0 * (1.0 - TR.busy_ns(rec) / (hi - lo))
