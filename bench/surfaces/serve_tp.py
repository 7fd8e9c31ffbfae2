"""The serving surface on a tensor-parallel mesh: ``serve.Cell`` with
all of its set-up, window and comparison, and two differences.

* A traced run profiles the window's last ``TRACE_SECONDS`` only.  On
  four chips a decode call of 36 layers is 2,226 device ops a chip, and
  writing out a 4.8 s slice (2.1M op events over the four chips) took
  ~50 s on the v5e host: a whole 50 s window would pass the profiler's
  buffer (~6.2M op events) and take minutes to write (PERF.md).  The
  profiler starts before the first engine step due at or after that
  point, and ``record()`` keeps the steps, calls and first chunks that
  started in the traced slice, so that per-layer readers compare host
  records and device time of the same calls.
* ``correct``'s mean gap is held to this configuration's own limit,
  ``GAP_LIMIT``, set from its readings at 36 layers: sound bfloat16
  runs read up to 0.067 there, above the 16-layer cell's 0.05.
"""
from __future__ import annotations

import contextlib
import time

from bench.surfaces import serve

TRACE_SECONDS = 5.0
# limit of the mean gap (logit units) at 36 bf16 layers: sound runs read
# 0.0187-0.0673 over 8 windows on 4 seeds, the float8 control 0.2135-0.2454
# on 3 seeds; 0.12 lies 1.8x from each (PERF.md, section 4)
GAP_LIMIT = 0.12


class Cell(serve.Cell):
    t_traced = None

    def measure(self, seconds: float, traced=contextlib.nullcontext) -> dict:
        """The window; ``traced`` is entered before the first step due in
        its last ``TRACE_SECONDS`` and left when the window ends."""
        t_trace = time.perf_counter() + max(0.0, seconds - TRACE_SECONDS)
        step = self.engine.step

        with contextlib.ExitStack() as stack:
            def start_trace():
                stack.enter_context(traced())
                self.t_traced = time.perf_counter()

            def step_traced_late():
                if self.t_traced is None and time.perf_counter() >= t_trace:
                    start_trace()
                step()

            self.engine.step = step_traced_late
            try:
                return self._window(seconds)
            finally:
                del self.engine.step
                if self.t_traced is None:     # no step came in the slice
                    start_trace()

    def record(self) -> dict:
        t0 = self.t_traced
        rec = super().record()
        rec["steps"] = [s for s in rec["steps"] if s["start"] >= t0]
        rec["calls"] = [c for c in rec["calls"] if c["start"] >= t0]
        rec["queue_ms"] = [(r["first_chunk"] - r["arrival"]) * 1e3
                           for r in self.reqs
                           if r["first_chunk"] is not None
                           and r["first_chunk"] >= t0]
        return rec

    def check(self):
        return [(n, v, GAP_LIMIT if n == "mean_gap" else lim)
                for n, v, lim in super().check()]
