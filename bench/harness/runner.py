"""One run of one cell: set up, measure, check, print.

The flow is the same for every surface; what a surface does is in
``bench/surfaces/<surface>.py``, which provides ``Cell(config, traffic,
seed, devices, seconds)`` with:

* ``setup()``           load, build and warm up every shape the window uses;
* ``measure(seconds, traced)``  the timed window; returns the end-to-end
                        values.  ``traced()`` is a context manager that the
                        surface puts around the part of the window a
                        traced run profiles (a no-op in an untraced run);
                        the profiler's buffer holds a few million device
                        ops, so a surface whose calls run long scans
                        profiles one call, not the whole window;
* ``record()``          what per-layer readers take from host clocks;
* ``release()``         free the program's device state;
* ``check()``           ``[(name, value, limit)]``: the comparison with the
                        plain reference that decides ``correct``.
"""
from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Optional

from bench.harness import cell as C
from bench.harness import trace as TR

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def use_compile_cache(root: Path) -> str:
    """JAX's persistent cache in ``$JAX_COMPILATION_CACHE_DIR`` or at the
    fixed ``<checkout>/.jax_cache``; every program is cached, however
    fast it compiles."""
    import jax
    path = os.environ.get(CACHE_DIR_ENV) or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def accelerator(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found {devs[0].platform}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs


class CompileCounter:
    """Counts traces and compiles while ``on`` (the window)."""

    def __init__(self):
        import jax
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self.on and name in COMPILE_EVENTS:
            self.n += 1


def peak_bytes(devs) -> Optional[int]:
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


@contextlib.contextmanager
def profiled(logdir: Path):
    """Profile the device and the ``bench.`` host spans into ``logdir``;
    the traced window is the ``bench.window`` span.  Python's own calls
    are not traced: that tracer slows the host path it would observe."""
    import jax
    shutil.rmtree(logdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(TR.WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: Path = C.ROOT, bm: Optional[dict] = None,
             overrides: Optional[dict] = None,
             devices: Optional[Callable] = None) -> dict:
    """Run cell ``name`` once; returns the result object.  ``devices``
    replaces the chip check (tests on the CPU), ``overrides`` replaces
    parts of the configuration or traffic (tests at small sizes)."""
    bm = bm if bm is not None else C.load_benchmark(root)
    w, cfg, traffic = C.load_cell(bm, name, root)
    if overrides:
        cfg = {**cfg, **overrides.get("config", {})}
        traffic = {**traffic, **overrides.get("traffic", {})}
    chips = int(w["chips"])
    cache_dir = use_compile_cache(root)
    devs = (devices or accelerator)(chips)
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    log(f"bench: cell {name} seed {seed} on {d0.platform} "
        f"{d0.device_kind!r} x{len(devs)}; compile cache {cache_dir}")
    counter = CompileCounter()
    mod = C.surface(cfg["surface"], root)
    cellobj = mod.Cell(cfg, traffic, seed, devs[:chips], seconds)
    cellobj.setup()
    logdir = root / ".bench_trace"
    traced = (lambda: profiled(logdir)) if trace else contextlib.nullcontext
    counter.on = True
    setup_s = time.perf_counter() - t_start
    log(f"bench: set-up {setup_s:.1f} s")
    e2e = cellobj.measure(seconds, traced)
    counter.on = False
    log(f"bench: window closed at {time.perf_counter() - t_start:.1f} s; "
        f"compilations inside the window: {counter.n}")
    device["memory_peak_bytes"] = peak_bytes(devs[:chips])
    rec = None
    if trace:
        rec = TR.load(TR.find_xplane(str(logdir)))
        shutil.rmtree(logdir, ignore_errors=True)
        log(f"bench: trace read at {time.perf_counter() - t_start:.1f} s")
    surface_rec = cellobj.record()
    cellobj.release()
    checks = cellobj.check()
    log(f"bench: compared with the reference at "
        f"{time.perf_counter() - t_start:.1f} s")
    correct = all(v <= lim for _, v, lim in checks) and counter.n == 0
    e2e["setup_s"] = setup_s
    units = {m["name"]: m["unit"] for m in bm["end_to_end"]}
    if trace:
        lo, hi = TR.window(rec)
        device["busy_s"] = TR.busy_ns(rec) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        full = {**rec, "surface": surface_rec, "device_kind": d0.device_kind,
                "chips": chips}
        metrics = C.read_per_layer(C.per_layer(bm, name), full, root)
        breakdown = {"device_ops": TR.top_ops(rec),
                     "idle_gaps": TR.idle_gaps(rec)}
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": units[m["name"]]}
                   for m in C.end_to_end(bm, name)}
        breakdown = None
    attempted, failed = cellobj.attempted, cellobj.failed
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    out["checks"]["compiles_in_window"] = {"value": counter.n, "limit": 0}
    for n, v, lim in checks:
        log(f"check {n}: {v!r} (limit {lim!r}) "
            f"{'ok' if v <= lim else 'FAILED'}")
    log(f"check compiles_in_window: {counter.n} (limit 0) "
        f"{'ok' if counter.n == 0 else 'FAILED'}")
    return out


def emit(out: dict) -> None:
    print(json.dumps(out), flush=True)
