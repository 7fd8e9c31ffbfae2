#!/usr/bin/env python3
"""Smoke test of both device surfaces on a TPU, through their entry points.

    python3 chip_smoke.py             # one chip: sweep phase + serve phase
    python3 chip_smoke.py --chips 4   # four chips: sharded serving only

One chip:

* sweep: ``fig9_congestor_victim`` with a 256-seed replica axis through
  ``repro.launch.sweep.build_sweep`` / ``run_sweep`` in f32
  (``precision="fast"``, auto impl, so the compiled Pallas WLBVT select
  kernel runs).  The compiled launch must contain the kernel
  (``tpu_custom_call``); per-tenant counts and times on sampled replicas
  must agree with the host oracle within the f32 tolerances below.  A
  small f64 leg (``precision="exact"``, impl ``jnp``) reports whether it
  is bit-identical to the host oracle; that answer does not fail the run.
* serve: Qwen3-8B at published widths, cut to 16 of 36 layers, bf16
  weights (the chip's share of a deployment whose other layers would be
  further pipeline stages) through ``ServeRuntime.from_spec`` +
  ``ModelExecutor`` on ``serve_mixed_slo``; then decode-through-the-cache
  logits against one full-sequence prefill.

Four chips (``--chips 4``): all 36 layers in bf16 on a ``(data=1,
model=4)`` mesh answer the same requests, each device must hold a quarter
of the weights, and at the 16-layer cut the sharded decode logits must
match the same steps run unsharded on device 0.

Every time printed is measured on the chip: without a TPU the script
exits non-zero before any phase.  The last line of standard output is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SWEEP_SCENARIO = "fig9_congestor_victim"
SWEEP_REPLICAS = 256
ORACLE_SAMPLE = 8          # replicas checked against the host oracle
EXACT_REPLICAS = 8
COUNT_FIELDS = ("completed", "killed", "drops", "ecn_marks")
# f32 tolerances of the fast sweep against the f64 host oracle.
# Counts: f32 event times can reorder an arrival and a completion that
# the f64 host separates by less than the f32 rounding; the arrival then
# sees a queue one packet longer or shorter, which can flip its ECN-mark
# or drop decision.  Measured with the f32 launch on CPU over 64 seeds of
# this scenario: one flip (seed 0, one ECN mark in 5361; the chip showed
# the same flip), 1.9e-4 relative.  Bounded at 1e-3 of each count, so a
# count the host gives as 0 must be 0.  Times: every

# grant sets a finish time t + dma + cycles * ns_per_cycle, rounded to f32
# (unit roundoff 2**-24 ~ 6e-8), from the previous event time, so the end
# time carries one chain of ~1e4-1e5 roundings: ~sqrt(N) * 6e-8 ~ 1e-5
# relative (measured 1.1e-5 on CPU), bounded here at 1e-4.  A kernel time
# is the difference of two event times of that run, so its error is a few
# f32 ulps of the end time, not of the (much smaller) kernel time:
# bounded at 2e-6 * end time (~32 ulps).
COUNT_RTOL = 1e-3
END_TIME_RTOL = 1e-4
KERNEL_TIME_ATOL_PER_END = 2e-6

SERVE_ARCH = "qwen3-8b"
SERVE_LAYERS_ONE_CHIP = 16
SERVE_SCENARIO = "serve_mixed_slo"
SERVE_TENANTS = 3
SERVE_REQUESTS = 12
MAX_SLOTS = 8
MAX_LEN = 2048
PREFILL_CHUNK = 256
DECODE_STEPS = 8
# bf16 logits tolerance (relative L2 over the vocab).  Weights and the
# residual stream are bf16 (8 significant bits, unit roundoff 2**-9 ~
# 2e-3).  Prefill and decode, or a sharded and an unsharded program, are
# different XLA programs that round at different points (fusion, tiling,
# partial-sum order), and the difference compounds over the layers: on
# CPU at reduced widths decode vs prefill measured 1.3e-2 at 2 layers and
# 1.9e-2 at 16.  A decode that reads the cache one position off measured
# 0.44 there, and unrelated logits give ~1.4.  1e-1 lies between.
LOGITS_REL_L2 = 1e-1
WEIGHT_SHARE_TOL = 0.01    # |device share - 1/4| of the weight bytes


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def peak_bytes(device):
    return (device.memory_stats() or {}).get("peak_bytes_in_use",
                                             "not measured")


# ---------------------------------------------------------------------------
# sweep phase
# ---------------------------------------------------------------------------
def require_kernel(hlo_text: str) -> None:
    """The compiled launch must hold the Pallas kernel as a TPU custom
    call (interpret mode would have lowered it to plain HLO)."""
    require("tpu_custom_call" in hlo_text,
            "compiled sweep launch has no tpu_custom_call "
            "(Pallas kernel not compiled in)")


def check_against_oracle(spec, row) -> list:
    """Raise unless ``row`` agrees with the host oracle within the f32
    tolerances; returns the counts that are not identical."""
    from repro.sim.devicepath import host_oracle
    h = host_oracle(spec, record_completions=False)
    marks = h.telemetry.counter("ecn_marks")
    end = h.time
    require(abs(row["time_ns"] - end) <= END_TIME_RTOL * end,
            f"seed {spec.seed}: end time {row['time_ns']} vs host {end}")
    atol = KERNEL_TIME_ATOL_PER_END * end
    flips = []
    for i, t in enumerate(row["tenants"]):
        hs = h.stats[i]
        host = {"completed": hs.completed, "killed": hs.killed,
                "drops": hs.drops, "ecn_marks": int(marks[i])}
        for k in COUNT_FIELDS:
            require(abs(t[k] - host[k]) <= COUNT_RTOL * host[k],
                    f"seed {spec.seed} tenant {i}: {k} {t[k]} vs host "
                    f"{host[k]}")
            if t[k] != host[k]:
                flips.append(f"seed {spec.seed} tenant {i} {k} {t[k]} vs "
                             f"host {host[k]}")
        for q in (50, 99):
            hv = hs.kernel_time_percentile(q)
            dv = t[f"p{q}_kernel_ns"]
            require(abs(dv - hv) <= atol,
                    f"seed {spec.seed} tenant {i}: p{q} kernel time "
                    f"{dv} vs host {hv} (atol {atol})")
    return flips


def sweep_phase(replicas: int = SWEEP_REPLICAS, sample: int = ORACLE_SAMPLE,
                exact_replicas: int = EXACT_REPLICAS, params=None) -> dict:
    from repro.launch.sweep import build_sweep, run_sweep
    from repro.sim.devicepath import (host_oracle, lower_sweep,
                                      parity_mismatches, run_sweep_specs)
    params = dict(params or {})
    sweep = build_sweep(SWEEP_SCENARIO, params, [], replicas)
    specs = [spec for _, spec in sweep.replicas()]
    say("sweep", f"{SWEEP_SCENARIO}: {len(specs)} replicas (seed axis), "
                 f"precision=fast, impl=auto")
    t0 = time.perf_counter()
    compiled = lower_sweep(specs, precision="fast").compile()
    compile_s = time.perf_counter() - t0
    require_kernel(compiled.as_text())
    say("sweep", "compiled launch contains tpu_custom_call (Pallas WLBVT "
                 "select kernel compiled, not interpreted)")
    rows, wall_s = run_sweep(sweep, precision="fast")
    n_pkts = sum(t["completed"] + t["killed"] + t["drops"]
                 for r in rows for t in r["tenants"])
    say("sweep", f"compile_s={compile_s:.3f} wall_s={wall_s:.3f} "
                 f"(run_sweep: prep + launch + materialize, after compile) "
                 f"packets={n_pkts}")
    step = max(1, len(specs) // sample)
    checked = list(range(0, len(specs), step))[:sample]
    t0 = time.perf_counter()
    flips = [f for r in checked for f in check_against_oracle(specs[r],
                                                               rows[r])]
    say("sweep", f"fast leg agrees with host oracle on seeds {checked}: "
                 f"per-tenant {'/'.join(COUNT_FIELDS)} within "
                 f"{COUNT_RTOL:g}, end time within rtol {END_TIME_RTOL:g}, "
                 f"p50/p99 kernel time within {KERNEL_TIME_ATOL_PER_END:g} "
                 f"x end time (host oracle {time.perf_counter() - t0:.3f}s)")
    say("sweep", f"counts not identical to the host: "
                 f"{'; '.join(flips) or 'none'}")

    ex_specs = specs[:exact_replicas]
    t0 = time.perf_counter()
    ex = run_sweep_specs(ex_specs, impl="jnp", precision="exact",
                         record_completions=True)
    ex_s = time.perf_counter() - t0
    diffs, worst_end, worst_ksum = {}, 0.0, 0.0
    for s, d in zip(ex_specs, ex):
        h = host_oracle(s)
        diffs[s.seed] = parity_mismatches(s, h, d)
        worst_end = max(worst_end, abs(d.time - h.time) / h.time)
        for i in range(len(s.tenants)):
            hk = h.stats[i].kernel_time_sum
            worst_ksum = max(worst_ksum, abs(d.stats[i].kernel_time_sum - hk)
                             / max(hk, 1e-30))
    exact = not any(diffs.values())
    say("sweep", f"exact leg (f64, impl=jnp, R={len(ex_specs)}): "
                 f"compile+wall_s={ex_s:.3f} bit-identical to host oracle: "
                 f"{exact}; worst relative difference: end time "
                 f"{worst_end:.3e}, kernel-time sum {worst_ksum:.3e}")
    if not exact:
        for seed, bad in diffs.items():
            say("sweep", f"  seed {seed} differs in: {', '.join(bad) or '-'}")
    return {"compile_s": compile_s, "wall_s": wall_s, "exact_parity": exact}


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------
def serve_config(num_layers: int):
    from repro.configs import get_config
    return dataclasses.replace(get_config(SERVE_ARCH), num_layers=num_layers,
                               param_dtype="bfloat16")


def serve_requests(cfg, seed: int, mesh=None, phase: str = "serve"):
    """``serve_mixed_slo`` through ServeRuntime + ModelExecutor (the
    ``launch/serve.py`` path); returns the executor after checking every
    request finished ``done`` with in-vocab tokens."""
    import numpy as np
    from repro.api import ServeRuntime, get_scenario
    from repro.serving.engine import ModelExecutor
    from repro.serving.request import RequestStatus
    spec = get_scenario(SERVE_SCENARIO, tenants=SERVE_TENANTS,
                        requests=SERVE_REQUESTS, max_slots=MAX_SLOTS,
                        max_len=MAX_LEN, prefill_chunk=PREFILL_CHUNK,
                        vocab=cfg.vocab_size, seed=seed)
    t0 = time.perf_counter()
    rt = ServeRuntime.from_spec(
        spec, executor=lambda ecfg: ModelExecutor(cfg, ecfg, mesh=mesh,
                                                  rng_seed=seed))
    exe = rt.engine.exe
    init_s = time.perf_counter() - t0
    B, C = MAX_SLOTS, PREFILL_CHUNK
    zi = np.zeros(B, np.int32)
    t0 = time.perf_counter()
    exe.fns.prefill_chunk.lower(exe.params, exe.cache,
                                np.zeros((B, C), np.int32), zi, zi).compile()
    zp = np.zeros(exe.width, np.int32)      # what the engine's chunks run
    exe.fns.prefill_rows.lower(exe.params, exe.cache,
                               np.zeros((exe.width, C), np.int32), zp, zp,
                               zp).compile()
    exe.fns.decode.lower(exe.params, exe.cache, zi, zi,
                         np.zeros(B, bool)).compile()
    exe.fns.reset_slots.lower(exe.cache, np.zeros(B, bool)).compile()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    rep = rt.run(spec)
    wall_s = time.perf_counter() - t0
    done = rt.engine.done
    require(len(done) == SERVE_REQUESTS,
            f"{len(done)} of {SERVE_REQUESTS} requests finished")
    for r in done:
        require(r.status == RequestStatus.DONE,
                f"request {r.rid} ended {r.status.value}")
        toks = np.asarray(r.generated)
        require(toks.size > 0 and toks.min() >= 0
                and toks.max() < cfg.vocab_size,
                f"request {r.rid} has token ids outside [0, vocab)")
    n_tok = sum(len(r.generated) for r in done)
    say(phase, f"init_s={init_s:.3f} (weights + cache on device, incl. "
               f"init compile) compile_s={compile_s:.3f} (prefill, decode, reset) "
               f"wall_s={wall_s:.3f} (engine run, {rep.extras['prefill_chunks']}"
               f" prefill chunks, {rep.extras['decode_steps']} decode steps)")
    say(phase, f"{len(done)}/{SERVE_REQUESTS} requests done, {n_tok} "
               f"generated tokens, all ids in [0, {cfg.vocab_size})")
    return exe


def rel_l2(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def prefill_rows(fns, params, cache, seqs, B: int, C: int):
    """Clear every slot, prefill ``seqs[b]`` into slot b in one chunk;
    returns (last logits (B, V) as f32 numpy, next tokens, cache)."""
    import jax.numpy as jnp
    import numpy as np
    cache = fns.reset_slots(cache, jnp.zeros(B, bool))
    toks = np.zeros((B, C), np.int32)
    n = np.zeros(B, np.int32)
    for b, s in enumerate(seqs):
        toks[b, :len(s)] = s
        n[b] = len(s)
    nxt, last, cache = fns.prefill_chunk(params, cache, toks,
                                         np.zeros(B, np.int32), n)
    return np.asarray(last, np.float32), np.asarray(nxt), cache


def decode_rows(fns, params, cache, seqs, tokens, B: int):
    """One decode step: append ``tokens[b]`` to the non-empty ``seqs[b]``
    (mutated) at position len(seqs[b]); returns (logits, next, cache)."""
    import numpy as np
    tok = np.zeros(B, np.int32)
    lengths = np.zeros(B, np.int32)
    active = np.zeros(B, bool)
    for b, s in enumerate(seqs):
        if s:
            tok[b] = tokens[b]
            lengths[b] = len(s)
            active[b] = True
            s.append(int(tokens[b]))
    nxt, logits, cache = fns.decode(params, cache, tok, lengths, active)
    return np.asarray(logits, np.float32), np.asarray(nxt), cache


def decode_matches_prefill(exe, seed: int, prompt_len: int = 200,
                           steps: int = DECODE_STEPS) -> float:
    """Slot 0: prefill a prompt, decode ``steps`` greedy tokens through
    the cache; each step's logits against one full-sequence prefill of
    the same tokens.  Returns the worst relative L2."""
    import numpy as np
    fns, params, cfg = exe.fns, exe.params, exe.fns.cfg
    B, C = MAX_SLOTS, PREFILL_CHUNK
    require(prompt_len + steps <= C, "check sequence must fit one chunk")
    rng = np.random.RandomState(seed)
    seqs = [list(rng.randint(1, cfg.vocab_size, size=prompt_len))]
    seqs += [[] for _ in range(B - 1)]
    _, nxt, cache = prefill_rows(fns, params, exe.cache, seqs, B, C)
    got = []
    for _ in range(steps):
        logits, nxt, cache = decode_rows(fns, params, cache, seqs, nxt, B)
        got.append(logits[0])
    full = seqs[0]
    worst = 0.0
    for j in range(steps):
        ref, _, cache = prefill_rows(
            fns, params, cache, [full[:prompt_len + j + 1]], B, C)
        worst = max(worst, rel_l2(got[j], ref[0]))
    exe.cache = cache
    return worst


def serve_phase(cfg, seed: int) -> dict:
    t0 = time.perf_counter()
    exe = serve_requests(cfg, seed)
    worst = decode_matches_prefill(exe, seed)
    require(worst <= LOGITS_REL_L2,
            f"decode-through-cache logits differ from full prefill: "
            f"rel L2 {worst:.3e} > {LOGITS_REL_L2:g}")
    say("serve", f"prefill + {DECODE_STEPS} decode steps through the cache "
                 f"== full-sequence prefill: worst rel L2 {worst:.3e} "
                 f"<= {LOGITS_REL_L2:g}")
    return {"wall_s": time.perf_counter() - t0, "logits_rel_l2": worst}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def weight_shares(params, devices) -> list:
    import jax
    per = {d: 0 for d in devices}
    total = 0
    for leaf in jax.tree.leaves(params):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per[shard.device] += shard.data.nbytes
    return [per[d] / total for d in devices]


def sharded_logits(cfg, seed: int, mesh=None, params=None, tokens=None,
                   steps: int = 4):
    """Prefill 8 prompts, then ``steps`` decode steps on every slot, with
    the serve programs on ``mesh`` (None: unsharded, default device).
    ``tokens`` (from the reference run) pins the decode inputs; returns
    (params, logits per step, tokens fed)."""
    import jax
    import numpy as np
    from repro.serving.serve_step import build_serve_fns
    B, C = MAX_SLOTS, PREFILL_CHUNK
    fns = build_serve_fns(cfg, mesh, batch=B, max_len=MAX_LEN,
                          prefill_chunk=C)
    if params is None:
        params = fns.init_params(jax.random.PRNGKey(seed))
    elif mesh is not None:
        params = jax.device_put(params, fns.param_shardings)
    rng = np.random.RandomState(seed)
    seqs = [list(rng.randint(1, cfg.vocab_size, size=C // 4 + b * C // (2 * B)))
            for b in range(B)]
    out, fed = [], []
    last, nxt, cache = prefill_rows(fns, params, fns.init_cache(), seqs, B, C)
    out.append(last)
    for k in range(steps):
        feed = nxt if tokens is None else tokens[k]
        fed.append(np.asarray(feed))
        logits, nxt, cache = decode_rows(fns, params, cache, seqs, feed, B)
        out.append(logits)
    del cache
    return params, out, fed


def four_chip_phase(seed: int) -> None:
    import jax
    from repro.launch.mesh import make_mesh
    devices = jax.devices()[:4]
    require(len(devices) == 4, f"--chips 4 needs 4 devices, JAX found "
                               f"{len(jax.devices())}")
    mesh = make_mesh((1, 4), ("data", "model"), devices=devices)
    cfg = serve_config(get_full_layers())
    say("4chip", f"{SERVE_ARCH} all {cfg.num_layers} layers, bf16, mesh "
                 f"(data=1, model=4) with Auto axes")
    exe = serve_requests(cfg, seed, mesh=mesh, phase="4chip")
    shares = weight_shares(exe.params, devices)
    say("4chip", "weight share per device: "
                 + " ".join(f"{s:.4f}" for s in shares)
                 + "; bytes_in_use: "
                 + " ".join(str((d.memory_stats() or {}).get(
                     "bytes_in_use", "not measured")) for d in devices))
    for s in shares:
        require(abs(s - 0.25) <= WEIGHT_SHARE_TOL,
                f"weights not split four ways: shares {shares}")
    del exe
    gc.collect()

    cut = serve_config(SERVE_LAYERS_ONE_CHIP)
    t0 = time.perf_counter()
    params, ref, fed = sharded_logits(cut, seed)
    _, got, _ = sharded_logits(cut, seed, mesh=mesh, params=params,
                               tokens=fed)
    del params
    worst = max(rel_l2(g, r) for g, r in zip(got, ref))
    require(worst <= LOGITS_REL_L2,
            f"sharded logits differ from one chip: rel L2 {worst:.3e}")
    say("4chip", f"{cut.num_layers}-layer cut: sharded prefill + decode "
                 f"logits == unsharded on device 0: worst rel L2 "
                 f"{worst:.3e} <= {LOGITS_REL_L2:g} "
                 f"(wall_s={time.perf_counter() - t0:.3f} incl. compile)")


def get_full_layers() -> int:
    from repro.configs import get_config
    return get_config(SERVE_ARCH).num_layers


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: sweep + serve phases; 4: sharded serving only")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and traces")
    args = ap.parse_args(argv)

    from repro.compile_cache import use_persistent_cache
    cache_dir = use_persistent_cache()
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {d0.platform}; "
              f"nothing was run", file=sys.stderr)
        return 2
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    print(f"device: {d0.platform} {d0.device_kind!r} x{len(devices)}; all "
          f"times below are measured on this chip, compile cache "
          f"{cache_dir}", flush=True)
    t_all = time.perf_counter()
    if args.chips == 4:
        four_chip_phase(args.seed)
    else:
        cfg = serve_config(SERVE_LAYERS_ONE_CHIP)
        print(f"serve config: {SERVE_ARCH} published widths, "
              f"{cfg.num_layers} of {get_full_layers()} layers, bf16 "
              f"weights: this chip's share of a deployment whose other "
              f"layers would be further pipeline stages", flush=True)
        sweep = sweep_phase()
        say("sweep", f"peak_bytes_in_use={peak_bytes(d0)}")
        serve = serve_phase(cfg, args.seed)
        say("serve", f"phase wall_s={serve['wall_s']:.3f} "
                     f"peak_bytes_in_use={peak_bytes(d0)}")
        print(f"exact (f64) sweep leg bit-identical to host oracle: "
              f"{sweep['exact_parity']}", flush=True)
    print(f"total wall_s={time.perf_counter() - t_all:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
