"""Multi-tenant serving surface: open-loop requests through the
``repro.serving`` engine (``Engine.step`` with ``ModelExecutor``'s jitted
prefill / decode / reset programs).

Set-up draws the weights from the seed on the device in one jitted call
(``bench/reference/qwen3.py``'s leaves, in the program's layout), builds
the executor, and runs a throwaway engine over one short request per
tenant, which compiles (or loads) the three programs of the cell's
shapes.  The window submits each request at its scheduled arrival time
and steps the engine while it has work; it sleeps until the next arrival
otherwise.  A step started in the window runs to its end, and the
window ends with it.

After the window the program's device state is freed and the plain f32
reference (``bench/reference/qwen3.py``) reruns a seeded sample of the
finished requests, the longest among them: every served token is
greedy, so on average its reference logit has to lie within the limit
of the reference's best.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import sys
import time
from typing import List

import numpy as np

from bench.harness import traffic as TF
from bench.harness import work as WK
from bench.reference import qwen3 as REF

STEP_SPAN = "bench.serve.step"
IDLE_SPAN = "bench.serve.idle"
CALL_SPANS = {"prefill": "bench.serve.prefill", "decode": "bench.serve.decode",
              "reset": "bench.serve.reset"}
# limit of the mean gap (logit units); its readings are in PERF.md
GAP_LIMIT = 0.05


def model_config(c: dict):
    """The program's ``ModelConfig`` with every published value of the
    configuration file set explicitly (the repo preset ties the
    embeddings; the published model does not)."""
    from repro.configs import get_config
    base = get_config(c["program_preset"])
    return dataclasses.replace(
        base, num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        qk_norm=True, qkv_bias=c["attention_bias"], mlp_act=c["hidden_act"],
        tie_embeddings=c["tie_word_embeddings"], dtype=c["torch_dtype"],
        param_dtype=c["torch_dtype"])


def program_params(c: dict, fns, seed: int):
    """The benchmark's weights in the program's parameter layout, made on
    the device in one jitted call; the layout is checked against the
    program's own ``init_params`` shapes."""
    import jax
    import jax.numpy as jnp
    sh = REF.shapes(c)
    L = c["num_hidden_layers"]
    dt = jnp.dtype(c["torch_dtype"])

    def make(key_data):
        def stack(name):
            return jax.lax.map(lambda l: REF.leaf(key_data, name, l, sh[name],
                                                  dt), jnp.arange(L))
        layer = {"norm1": stack("input_layernorm"),
                 "mixer": {"wq": stack("q_proj"), "wk": stack("k_proj"),
                           "wv": stack("v_proj"), "wo": stack("o_proj"),
                           "q_norm": stack("q_norm"),
                           "k_norm": stack("k_norm")},
                 "norm2": stack("post_attention_layernorm"),
                 "mlp": {"w_gate": stack("gate_proj"),
                         "w_up": stack("up_proj"),
                         "w_down": stack("down_proj")}}
        return {"embed": REF.leaf(key_data, "embed_tokens", 0,
                                  sh["embed_tokens"], dt),
                "final_norm": REF.leaf(key_data, "norm", 0, sh["norm"], dt),
                "lm_head": REF.leaf(key_data, "lm_head", 0, sh["lm_head"], dt),
                "front": [], "groups": (layer,), "tail": []}

    want = jax.eval_shape(fns.init_params, jax.random.PRNGKey(0))
    got = jax.eval_shape(make, jax.ShapeDtypeStruct((2,), jnp.uint32))
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the program's parameter layout differs from the "
                         "one the benchmark fills")
    out_sh = fns.param_shardings
    return jax.jit(make, out_shardings=out_sh)(REF.root_key(seed))


class TimedExecutor:
    """The program's executor with each call timed on the host clock,
    annotated for the profiler, and its required work counted."""

    def __init__(self, exe, model: dict):
        self.exe = exe
        self.model = model
        self.calls: List[dict] = []
        self.step_exec = 0.0

    def _timed(self, kind, fn, args, work):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(CALL_SPANS[kind]):
            out = fn(*args)
        dt = time.perf_counter() - t0
        self.step_exec += dt
        if work is not None:
            self.calls.append({"kind": kind, "wall": dt, "start": t0,
                               "flops": work[0], "bytes": work[1]})
        return out

    def prefill(self, tokens, lengths, valid_n):
        rows = [(int(lengths[b]), int(valid_n[b])) for b in range(len(valid_n))
                if valid_n[b] > 0]
        return self._timed("prefill", self.exe.prefill,
                           (tokens, lengths, valid_n),
                           WK.prefill_work(self.model, rows))

    def decode(self, tokens, lengths, active):
        ctx = [int(lengths[b]) for b in range(len(active)) if active[b]]
        return self._timed("decode", self.exe.decode, (tokens, lengths, active),
                           WK.decode_work(self.model, ctx))

    def reset(self, keep):
        return self._timed("reset", self.exe.reset, (keep,), None)


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 seconds: float):
        self.c = config
        self.seconds = float(seconds)
        self.mix = traffic
        self.seed = int(seed)
        self.devices = devices
        self.eng_cfg = config["engine"]
        self.attempted = self.failed = 0
        self.steps: List[dict] = []
        self.reqs: List[dict] = []

    # -- set-up ---------------------------------------------------------------
    def _engine(self, exe):
        from repro.core.slo import SLOPolicy
        from repro.serving.engine import Engine, EngineConfig
        e = self.eng_cfg
        tens = self.mix["tenants"]
        eng = Engine(EngineConfig(
            max_slots=e["max_slots"], max_len=e["max_len"],
            prefill_chunk=e["prefill_chunk"],
            prefill_slots_per_step=e["prefill_slots_per_step"],
            scheduler=e["scheduler"], arbiter=e["arbiter"],
            max_tenants=max(len(tens), 2)), executor=exe)
        for i, t in enumerate(tens):
            eng.create_ectx(i, SLOPolicy(
                priority=float(t["priority"]),
                kv_quota_tokens=int(t["kv_slots"]) * e["max_len"]),
                name=t["name"])
        return eng

    def setup(self) -> None:
        from repro.serving.engine import ModelExecutor, EngineConfig
        from repro.serving.request import Request
        mesh = None
        if len(self.devices) > 1:
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((1, len(self.devices)), ("data", "model"),
                             devices=self.devices)
        e = self.eng_cfg
        ecfg = EngineConfig(max_slots=e["max_slots"], max_len=e["max_len"],
                            prefill_chunk=e["prefill_chunk"])
        from repro.serving.serve_step import build_serve_fns
        cfg = model_config(self.c)
        fns = build_serve_fns(cfg, mesh, batch=e["max_slots"],
                              max_len=e["max_len"],
                              prefill_chunk=e["prefill_chunk"])
        params = program_params(self.c, fns, self.seed)
        self.exe = TimedExecutor(ModelExecutor(cfg, ecfg, params=params,
                                               mesh=mesh), self.c)
        warm = self._engine(self.exe)
        for i in range(len(self.mix["tenants"])):
            warm.submit(Request(i, np.arange(1, 9, dtype=np.int32),
                                max_new_tokens=2))
        warm.run_until_idle()
        self.exe.calls.clear()
        self.schedule = TF.serve_requests(self.mix, self.seed, self.seconds,
                                          self.c["vocab_size"])
        self.engine = self._engine(self.exe)

    # -- window ---------------------------------------------------------------
    def _busy(self) -> bool:
        eng = self.engine
        return (any(r is not None for r in eng.slot_req)
                or any(len(q) for q in eng.queues.values()))

    def measure(self, seconds: float, traced=contextlib.nullcontext) -> dict:
        """The window; a traced run profiles all of it."""
        with traced():
            return self._window(seconds)

    def _window(self, seconds: float) -> dict:
        import jax
        from repro.serving.request import Request, RequestStatus
        eng, exe, sched = self.engine, self.exe, self.schedule
        live: List[dict] = []
        t_open = time.perf_counter()
        t_close = t_open + seconds
        i = 0
        lateness = []
        while True:
            now = time.perf_counter()
            # every request due in the window is sent, the last ones
            # even when the step that ran past the close delayed them
            while i < len(sched) and t_open + sched[i]["t"] <= min(now,
                                                                   t_close):
                a = sched[i]
                req = Request(a["tenant"], a["prompt"],
                              max_new_tokens=a["max_new"])
                eng.submit(req)
                rec = {"arrival": t_open + a["t"], "tenant": a["tenant"],
                       "prompt": a["prompt"], "req": req, "times": [],
                       "first_chunk": None}
                lateness.append(now - rec["arrival"])
                self.reqs.append(rec)
                if req.status != RequestStatus.REJECTED:
                    live.append(rec)
                i += 1
            if now >= t_close:
                break
            if not self._busy():
                nxt = t_open + sched[i]["t"] if i < len(sched) else t_close
                with jax.profiler.TraceAnnotation(IDLE_SPAN):
                    time.sleep(max(0.0, min(nxt, t_close) - now))
                continue
            k = eng.step_count
            exe.step_exec = 0.0
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(STEP_SPAN):
                eng.step()
            t1 = time.perf_counter()
            self.steps.append({"start": t0, "end": t1, "exec": exe.step_exec})
            still = []
            for rec in live:
                req = rec["req"]
                if rec["first_chunk"] is None and req.chunk_steps:
                    rec["first_chunk"] = t0 if req.chunk_steps[0] == k else None
                new = len(req.generated) - len(rec["times"])
                rec["times"].extend([t1] * new)
                if req.status not in (RequestStatus.DONE,
                                      RequestStatus.KILLED):
                    still.append(rec)
            live = still
        t_end = max(t_close, self.steps[-1]["end"] if self.steps else t_close)
        self.window = (t_open, t_end)
        self.lateness = lateness
        self.attempted = len(self.reqs)
        self.failed = sum(r["req"].status == RequestStatus.REJECTED
                          for r in self.reqs)
        self.ttft = [((r["times"][0] if r["times"] else t_end)
                      - r["arrival"]) * 1e3 for r in self.reqs]
        itl = [(b - a) * 1e3 for r in self.reqs
               for a, b in zip(r["times"], r["times"][1:])]
        tokens = sum(len(r["times"]) for r in self.reqs)
        out = {"ttft_mean_ms": float(np.mean(self.ttft)),
               "ttft_p50_ms": float(np.percentile(self.ttft, 50)),
               "itl_p50_ms": float(np.percentile(itl, 50)) if itl else
               float("nan"),
               "itl_p95_ms": float(np.percentile(itl, 95)) if itl else
               float("nan"),
               "out_tok_per_s": tokens / (t_end - t_open)}
        print(f"bench: {len(self.reqs)} requests, {tokens} tokens, "
              f"{len(itl)} gaps; " + ", ".join(f"{k} {v!r}" for k, v in
                                             out.items()),
              file=sys.stderr, flush=True)
        return out

    def record(self) -> dict:
        return {"steps": self.steps, "calls": self.exe.calls,
                "queue_ms": [(r["first_chunk"] - r["arrival"]) * 1e3
                             for r in self.reqs
                             if r["first_chunk"] is not None]}

    def release(self) -> None:
        import jax
        self.engine = None
        inner = self.exe.exe
        self.exe.exe = None
        inner.params = inner.cache = None
        del inner
        gc.collect()
        jax.clear_caches()

    # -- correctness ----------------------------------------------------------
    def sample(self) -> List[dict]:
        """Finished requests drawn from the seed, the longest first, until
        ``check_tokens`` served tokens are covered."""
        from repro.serving.request import RequestStatus
        done = [r for r in self.reqs if r["req"].status == RequestStatus.DONE]
        if not done:
            return []
        size = [len(r["prompt"]) + len(r["req"].generated) for r in done]
        longest = int(np.argmax(size))
        order = [longest] + [int(j) for j in
                             np.random.default_rng(self.seed).permutation(
                                 len(done)) if j != longest]
        out, served = [], 0
        for j in order:
            if served >= int(self.mix["check_tokens"]) or \
                    len(out) >= int(self.mix["check_requests"]):
                break
            out.append(done[j])
            served += len(done[j]["req"].generated)
        return out

    def gaps(self, picked, fp8: bool = False):
        """Per sampled request, the reference logits at each served
        position and the served tokens (``fp8``: the control's logits)."""
        seqs, pos, toks = [], [], []
        for r in picked:
            gen = np.asarray(r["req"].generated, np.int32)
            p = len(r["prompt"])
            seqs.append(np.concatenate([r["prompt"], gen[:-1]]))
            pos.append(np.arange(p - 1, p - 1 + len(gen)))
            toks.append(gen)
        ref = REF.logits(self.c, self.seed, seqs, pos,
                         dtype=self.c["torch_dtype"], fp8=fp8)
        return ref, toks

    def check(self):
        """The mean gap of the sampled served tokens is compared.  The
        widest gap is logged beside it but not compared: in 16 bfloat16
        layers a few near-ties flip per run, and sound runs' widest gap
        came within 2x of the float8 control's (PERF.md)."""
        picked = self.sample()
        if not picked:
            return [("served_tokens_checked", 0, -1)]
        ref, toks = self.gaps(picked)
        self._ref = (picked, ref)
        gaps = np.concatenate([REF.served_gaps(l, t)
                               for l, t in zip(ref, toks)])
        log_gaps("served tokens", gaps)
        return [("mean_gap", float(gaps.mean()), GAP_LIMIT)]

    def control(self):
        """The precision control on the requests ``check`` compared: the
        reference with every projection in float8 (the step below the
        configuration's bfloat16) puts its own token first at each
        served position; the gap of that token in the f32 reference."""
        picked, ref = self._ref
        ctl, _ = self.gaps(picked, fp8=True)
        gaps = np.concatenate([REF.served_gaps(r, c.argmax(axis=-1))
                               for r, c in zip(ref, ctl)])
        log_gaps("float8 control", gaps)
        return [("mean_gap", float(gaps.mean()))]


def log_gaps(what: str, gaps: np.ndarray) -> None:
    print(f"bench: {what}: {len(gaps)} checked, mean gap "
          f"{float(gaps.mean())!r}, widest {float(gaps.max())!r}, not the "
          f"reference's best {float(np.mean(gaps > 0))!r}", file=sys.stderr,
          flush=True)
