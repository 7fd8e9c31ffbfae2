import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# ^ MUST precede any jax import: jax locks the device count on first init.
# The 512 host devices exist only for this dry-run driver; tests and
# other tools see the real single CPU device.  This tool compiles on
# virtual CPU devices only, so on a machine with a TPU it never takes it.
"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) cell.

For each cell this driver lowers the real jitted program (train_step for
train shapes, full-prompt prefill for prefill shapes, serve decode for
decode shapes) against ShapeDtypeStruct stand-ins on the production mesh
(16x16 single-pod / 2x16x16 multi-pod), compiles it, and records:

  * memory_analysis()  — per-device argument/output/temp bytes (fits HBM?)
  * cost_analysis()    — per-device HLO FLOPs + bytes accessed
  * collective bytes   — parsed from the post-SPMD HLO: operand bytes of
    every all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute, per primitive

Each record is printed; the exit code is non-zero when a cell fails to
lower or compile.  Usage:

    python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    python -m repro.launch.dryrun --all             # 40 cells x 2 meshes
    python -m repro.launch.dryrun --all --mesh single
"""
import argparse
import dataclasses
import re
import subprocess
import sys
import time
from typing import Dict, Optional

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1, "c64": 8, "c128": 16,
}
_SHAPE_RE = re.compile(r"([a-z]\w*)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s+=\s+(.*)$")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(s: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(s):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per-collective operand bytes from post-SPMD (per-device) HLO."""
    table: Dict[str, int] = {}
    pending = []
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, rest = m.group(1), m.group(2)
        paren = rest.find("(")
        head = rest[:paren] if paren >= 0 else rest
        table[name] = _shape_bytes(head)
        opcode = head.strip().split()[-1] if head.strip() else ""
        # opcode variants like "all-gather-start" / "-done" count once
        base = next((c for c in _COLLECTIVES
                     if opcode == c or opcode == c + "-start"), None)
        if base is not None and paren >= 0:
            depth, end = 0, paren
            for i, ch in enumerate(rest[paren:], paren):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    end = i
                    break
            operands = re.findall(r"%([\w\.\-]+)", rest[paren:end + 1])
            pending.append((base, operands))
    out: Dict[str, Dict[str, float]] = {
        c: {"bytes": 0.0, "count": 0} for c in _COLLECTIVES}
    for base, operands in pending:
        b = sum(table.get(o, 0) for o in operands)
        out[base]["bytes"] += float(b)
        out[base]["count"] += 1
    out["total_bytes"] = sum(v["bytes"] for v in out.values()
                             if isinstance(v, dict))
    return out


# ---------------------------------------------------------------------------
# per-kind lowering
# ---------------------------------------------------------------------------
def _train_grad_accum(cfg, shape) -> int:
    # keep per-chip microbatch small enough that fp32 logits fit comfortably
    accum = 8 if shape.global_batch >= 64 else 1
    while shape.global_batch % accum:
        accum //= 2
    return max(accum, 1)


def lower_train(cfg, shape, mesh, moe_impl: str,
                seq_parallel: bool = False):
    import jax
    from jax.sharding import NamedSharding
    from repro.launch.mesh import input_specs
    from repro.training.trainer import build_trainer
    from repro.training.train_state import TrainState

    trainer = build_trainer(cfg, mesh, grad_accum=_train_grad_accum(cfg, shape),
                            moe_impl=moe_impl, donate=True,
                            seq_parallel=seq_parallel)
    params_sds = jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0))
    state_sds = jax.eval_shape(
        lambda p: TrainState.create(p, trainer.optimizer), params_sds)
    state_sds = jax.tree.map(
        lambda s, spec: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
        state_sds, trainer.state_pspecs)
    batch_sds = input_specs(cfg, shape, mesh)
    return trainer.train_step.lower(state_sds, batch_sds)


def _serve_params_sds(model, cfg, mesh):
    """Serving weights are a bf16 copy of the fp32 training params."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.distributed import sharding as SH
    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = SH.param_pspecs(cfg, params_sds, mesh, "serve")
    dt = jnp.dtype(cfg.dtype)

    def cast(s, spec):
        d = dt if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype
        return jax.ShapeDtypeStruct(s.shape, d,
                                    sharding=NamedSharding(mesh, spec))

    return jax.tree.map(cast, params_sds, pspecs)


def lower_decode(cfg, shape, mesh, moe_impl: str):
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import cache_specs, input_specs
    from repro.serving.serve_step import build_serve_fns

    fns = build_serve_fns(cfg, mesh, batch=shape.global_batch,
                          max_len=shape.seq_len, moe_impl=moe_impl,
                          shard_cache_length=(shape.global_batch == 1))
    params_sds = _serve_params_sds(fns.model, cfg, mesh)
    cache_sds = cache_specs(cfg, shape, mesh, model=fns.model)
    inp = input_specs(cfg, shape, mesh)
    active = jax.ShapeDtypeStruct(inp["lengths"].shape, jnp.bool_,
                                  sharding=inp["lengths"].sharding)
    return fns.decode.lower(params_sds, cache_sds, inp["tokens"],
                            inp["lengths"], active)


def lower_prefill(cfg, shape, mesh, moe_impl: str):
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import cache_specs, input_specs
    from repro.serving.serve_step import build_serve_fns

    fns = build_serve_fns(cfg, mesh, batch=shape.global_batch,
                          max_len=shape.seq_len, moe_impl=moe_impl,
                          prefill_chunk=shape.seq_len)
    params_sds = _serve_params_sds(fns.model, cfg, mesh)
    cache_sds = cache_specs(cfg, shape, mesh, model=fns.model)
    inp = input_specs(cfg, shape, mesh)
    valid_n = jax.ShapeDtypeStruct(inp["lengths"].shape, jnp.int32,
                                   sharding=inp["lengths"].sharding)
    if cfg.is_encoder_decoder:
        # whisper: prefill carries the (stubbed) encoder frames
        def pf(params, cache, tokens, lengths, valid_n, frames):
            B, C = tokens.shape
            valid = jnp.arange(C)[None, :] < valid_n[:, None]
            logits, cache = fns.model.prefill(params, tokens, cache,
                                              lengths, valid=valid,
                                              frames=frames)
            return logits[:, -1], cache
        return jax.jit(pf, donate_argnums=(1,)).lower(
            params_sds, cache_sds, inp["tokens"], inp["lengths"], valid_n,
            inp["frames"])
    return fns.prefill_chunk.lower(params_sds, cache_sds, inp["tokens"],
                                   inp["lengths"], valid_n)


# ---------------------------------------------------------------------------
# cell driver
# ---------------------------------------------------------------------------
def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             moe_impl: str = "gshard",
             attn_impl: Optional[str] = None,
             seq_parallel: bool = False) -> Dict:
    import jax
    from repro.configs import SHAPES, cell_supported, get_config, param_count
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(arch)
    if attn_impl:
        cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
    shape = SHAPES[shape_name]
    mesh_name = "multipod" if multi_pod else "singlepod"
    rec: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "kind": shape.kind, "moe_impl": moe_impl,
                 "params": param_count(cfg)}
    ok, reason = cell_supported(cfg, shape)
    if not ok:
        rec["skipped"] = reason
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    rec["devices"] = int(mesh.size)
    t0 = time.time()
    if shape.kind == "train":
        lowered = lower_train(cfg, shape, mesh, moe_impl, seq_parallel)
    elif shape.kind == "prefill":
        lowered = lower_prefill(cfg, shape, mesh, moe_impl)
    else:
        lowered = lower_decode(cfg, shape, mesh, moe_impl)
    rec["lower_s"] = round(time.time() - t0, 1)

    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 1)

    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_bytes_est": int(ma.argument_size_in_bytes
                              + ma.output_size_in_bytes
                              + ma.temp_size_in_bytes
                              - ma.alias_size_in_bytes),
    }
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, list):    # older jax wraps the dict in a list
        ca = ca[0] if ca else {}
    # raw XLA numbers (while bodies counted ONCE — kept for reference)
    rec["cost_xla_once"] = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
    # trip-count-aware walk of the post-SPMD HLO (launch/hlo_stats.py):
    # the numbers the record reports
    from repro.launch.hlo_stats import analyze as hlo_analyze
    hs = hlo_analyze(compiled.as_text())
    rec["cost"] = {"flops": hs["flops"], "bytes_accessed": hs["bytes"]}
    rec["collectives"] = {
        **{c: {"bytes": hs[c], "count": int(hs[c + "_count"])}
           for c in ("all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all", "collective-permute")},
        "total_bytes": hs["collective_bytes"],
    }
    return rec


def _print_rec(rec: Dict) -> None:
    if "skipped" in rec:
        print(f"[skip] {rec['arch']} x {rec['shape']} x {rec['mesh']}: "
              f"{rec['skipped']}")
        return
    m = rec["memory"]
    c = rec["collectives"]
    print(f"[ ok ] {rec['arch']} x {rec['shape']} x {rec['mesh']} "
          f"(lower {rec['lower_s']}s, compile {rec['compile_s']}s)")
    print(f"       mem/device: args {m['argument_bytes']/2**30:.2f} GiB, "
          f"temp {m['temp_bytes']/2**30:.2f} GiB, "
          f"out {m['output_bytes']/2**30:.2f} GiB")
    print(f"       flops/device: {rec['cost']['flops']:.3e}   "
          f"collective bytes/device: {c['total_bytes']:.3e}")
    per = {k: v for k, v in c.items()
           if isinstance(v, dict) and v["count"]}
    if per:
        print("       " + "  ".join(
            f"{k}:{v['count']}x/{v['bytes']:.2e}B" for k, v in per.items()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--moe-impl", default="gshard")
    ap.add_argument("--attn-impl", default=None)
    ap.add_argument("--seq-parallel", action="store_true",
                    help="sequence-parallel residual stream (train cells)")
    ap.add_argument("--subprocess-per-cell", action="store_true",
                    help="isolate each cell in a fresh process (RAM hygiene)")
    args = ap.parse_args(argv)

    from repro.configs import SHAPES, list_archs
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    if args.all:
        cells = [(a, s) for a in list_archs() for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            if args.subprocess_per_cell and len(cells) > 1:
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape,
                       "--mesh", "multi" if mp else "single",
                       "--moe-impl", args.moe_impl]
                if args.attn_impl:
                    cmd += ["--attn-impl", args.attn_impl]
                if args.seq_parallel:
                    cmd += ["--seq-parallel"]
                r = subprocess.run(cmd)
                failures += (r.returncode != 0)
                continue
            try:
                rec = run_cell(arch, shape, mp, moe_impl=args.moe_impl,
                               attn_impl=args.attn_impl,
                               seq_parallel=args.seq_parallel)
                _print_rec(rec)
            except Exception as e:  # noqa: BLE001
                failures += 1
                print(f"[FAIL] {arch} x {shape} x "
                      f"{'multipod' if mp else 'singlepod'}: {e!r}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
