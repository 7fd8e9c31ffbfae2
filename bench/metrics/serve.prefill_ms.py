"""Data plane, prefill: mean wall time of the executor's prefill calls in
the window, including the host's wait for the next tokens.  Milliseconds,
host clock."""


def read(rec):
    w = [c["wall"] for c in rec["surface"].get("calls", ())
         if c["kind"] == "prefill"]
    return sum(w) * 1e3 / len(w) if w else None
