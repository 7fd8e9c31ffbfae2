"""Device time of the exchange between chips inside one serving program.

An XLA op's event in the trace is named by its whole HLO instruction
(``%all-reduce.5 = bf16[8,1,4096]{...} all-reduce(...), ...``).  The
exchange is every op that is itself a collective, by instruction name
or opcode: ``all-reduce``, ``all-gather``, ``reduce-scatter``,
``collective-permute``, ``all-to-all``, each with its ``-start`` /
``-done`` halves, and the TPU compiler's asynchronous collectives
(``%async-collective-start``, ``%async-collective-done``).  The fusions
the compiler builds around an asynchronous collective (their
instruction calls an ``%async_collective_fusion`` computation) compute
besides and are left out (PERF.md, section 3).

Per call of a program: on each chip, the union of those ops' intervals
inside each of the program's module executions in the window (so that
overlapping halves count once), summed; averaged over the chips and
divided by the executions.
"""
from __future__ import annotations

import re
from typing import Optional

import numpy as np

from bench.harness import trace as TR

KINDS = r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
COLLECTIVE = re.compile(
    rf"^%?(?:{KINDS}|async-collective)(?:-start|-done)?\b"
    rf"|\s(?:{KINDS})(?:-start|-done)?\(")


def is_collective(name: str) -> bool:
    return COLLECTIVE.search(name) is not None


def exchange_ms(rec: dict, kind: str) -> Optional[float]:
    """Mean exchange time per execution of the ``jit__<kind>`` module in
    the window, in milliseconds; None where no execution holds an
    exchange op."""
    lo, hi = TR.window(rec)
    prefix = "jit__" + kind
    total, calls, found = 0.0, 0, False
    devs = rec["devices"]
    for d in devs:
        mnames, ms, me = d["modules"]
        names, s, e = d["ops"]
        keep = np.fromiter((is_collective(n) for n in names), bool,
                           len(names))
        cs, ce = s[keep], e[keep]
        order = np.argsort(cs, kind="stable")
        cs, ce = cs[order], ce[order]
        for n, a, b in zip(mnames, ms, me):
            if not (n.startswith(prefix) and lo <= a <= hi):
                continue
            calls += 1
            i, j = np.searchsorted(cs, [a, b], side="left")
            inside = TR.merge(cs[i:j], ce[i:j], a, b)
            found = found or bool(inside)
            total += TR.length(inside)
    if not found or not calls:
        return None
    return total / calls * 1e-6
