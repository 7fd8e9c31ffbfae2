"""Admission and queueing: 90th percentile, over the requests whose
first prefill chunk ran in the window, of the time from the request's
scheduled arrival to the start of the engine step that ran that chunk.
Milliseconds, host clock."""
import numpy as np


def read(rec):
    q = rec["surface"].get("queue_ms") or []
    return float(np.percentile(q, 90)) if q else None
