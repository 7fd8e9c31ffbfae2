"""Tensor-parallel exchange, decode: per decode call, the device time of
the collective ops between the chips inside the ``jit__decode`` module
executions of the traced window (bench/harness/collectives.py: union per
chip, so overlapping ``-start`` / ``-done`` halves count once; averaged
over the chips).  Milliseconds."""
from bench.harness.collectives import exchange_ms


def read(rec):
    return exchange_ms(rec, "decode")
