"""Prefill program against its roofline: the least time the chip needs
for the work the valid tokens require (bench/harness/work.py: matmuls,
attention over the real context, one row of logits per sequence; bytes
of the weights and KV read), over the device time of the prefill
program's executions in the window.  Percent."""
from bench.harness.serve_calls import roofline


def read(rec):
    return roofline(rec, "prefill")
