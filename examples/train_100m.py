"""End-to-end training driver example: a ~100M-param qwen3-family model
trained for a few hundred steps on synthetic Markov data, with sharding
(if multiple devices are forced), grad accumulation, checkpointing and
resume.

    PYTHONPATH=src python examples/train_100m.py --steps 300
    # multi-device data/tensor parallel on forced host devices:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/train_100m.py --steps 300 --mesh 2x4
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.training import checkpoint as CKPT
from repro.training.data import make_pipeline
from repro.training.trainer import build_trainer


def config_100m():
    """qwen3 family scaled to ~100M params."""
    base = get_config("qwen3-8b")
    return dataclasses.replace(
        base, name="qwen3-100m", num_layers=6, d_model=512, num_heads=8,
        num_kv_heads=4, head_dim=64, d_ff=2048, vocab_size=32_000,
        attn_chunk=256, learning_rate=6e-4)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--grad-accum", type=int, default=2)
    ap.add_argument("--mesh", default="none")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_100m_ckpt")
    args = ap.parse_args()

    cfg = config_100m()
    mesh = None
    if args.mesh != "none":
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))

    trainer = build_trainer(cfg, mesh=mesh, total_steps=args.steps,
                            warmup_steps=20, grad_accum=args.grad_accum)
    state = trainer.init_state(jax.random.PRNGKey(0))
    import numpy as np
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(state.params))
    print(f"params: {n/1e6:.1f}M   mesh: {args.mesh}")

    pipe = make_pipeline(cfg, args.seq_len, args.global_batch, prefetch=True)
    ckpt = CKPT.AsyncCheckpointer(args.ckpt_dir)
    bshard = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        bshard = NamedSharding(mesh, P("data", None))

    t0 = time.time()
    for step in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(pipe).items()}
        if bshard is not None:
            batch = {k: jax.device_put(v, bshard) for k, v in batch.items()}
        state, m = trainer.train_step(state, batch)
        if (step + 1) % 25 == 0:
            toks = args.global_batch * args.seq_len * (step + 1)
            print(f"step {step+1:4d}  loss {float(m['loss']):.4f}  "
                  f"gnorm {float(m['grad_norm']):.2f}  "
                  f"tok/s {toks/(time.time()-t0):,.0f}")
        if (step + 1) % 100 == 0:
            ckpt.save(state, step + 1,
                      extra={"step": step + 1, "data": pipe.state()})
    ckpt.wait()
    print(f"done; checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
