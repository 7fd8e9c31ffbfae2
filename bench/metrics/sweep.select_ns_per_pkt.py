"""WLBVT select per simulated packet: device time of the ``wlbvt_select``
kernel's executions inside each timed ``run_sweep_specs`` call, over the
packets those calls simulated.  An XLA op event is named by its HLO
instruction, and the Pallas kernel's custom call is named after the
kernel: ``%wlbvt_select.<n> = ...``.  The lane padding around the call,
and the ``jnp`` select, carry the name only in their op metadata, which
the trace's events do not hold, so they are not counted; a program whose
select has no such name reads nothing."""
import numpy as np

from bench.harness import trace as TR
from bench.harness.sweep_calls import CALL_SPAN

OP_PREFIX = "%wlbvt_select."


def read(rec):
    devs = rec["devices"]
    calls = rec["surface"].get("calls", [])
    ns = 0.0
    packets = 0
    for (a, b), call in zip(TR.spans(rec, CALL_SPAN), calls):
        for d in devs:
            names, s, e = d["ops"]
            idx = [i for i, n in enumerate(names) if n.startswith(OP_PREFIX)]
            s, e = np.asarray(s)[idx], np.asarray(e)[idx]
            inside = (s >= a) & (s <= b)
            ns += float((e[inside] - s[inside]).sum())
        packets += call["packets"]
    if ns <= 0 or not packets:
        return None
    return ns / len(devs) / packets
