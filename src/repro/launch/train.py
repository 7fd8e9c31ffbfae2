"""Training driver: data pipeline -> train loop -> checkpoints -> resume.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \
        --steps 50 --ckpt-dir /tmp/ckpt --ckpt-every 20

Fault tolerance: async sharded checkpoints with atomic commit; on restart
the driver resumes from LATEST (params+optimizer+data-iterator state), on
an arbitrary mesh (elastic resharding in training/checkpoint.py).  A
preemption signal (SIGTERM) triggers a final synchronous save.
"""
from __future__ import annotations

import argparse
import signal
import sys
import time

import jax
import jax.numpy as jnp


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--mesh", default="none",
                    help="none | dxm grid like 2x4 (data x model)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    from repro.configs import get_config, smoke_config
    from repro.launch.mesh import make_mesh
    from repro.training import checkpoint as CKPT
    from repro.training.data import make_pipeline
    from repro.training.trainer import build_trainer

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = None
    if args.mesh != "none":
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))

    trainer = build_trainer(cfg, mesh=mesh, total_steps=args.steps,
                            grad_accum=args.grad_accum)
    pipe = make_pipeline(cfg, args.seq_len, args.global_batch,
                         seed=args.seed)
    state = trainer.init_state(jax.random.PRNGKey(args.seed))

    start_step = 0
    ckpt = CKPT.AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    if args.resume and args.ckpt_dir:
        latest = CKPT.latest_step(args.ckpt_dir)
        if latest is not None:
            shardings = (jax.tree.map(lambda x: x.sharding, state)
                         if mesh is not None else None)
            state, extra = CKPT.load(args.ckpt_dir, state,
                                     shardings=shardings)
            pipe.restore(extra["data"])
            start_step = int(extra["step"])
            print(f"resumed from step {start_step}")

    stop = {"flag": False}
    signal.signal(signal.SIGTERM, lambda *_: stop.update(flag=True))

    bshard = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed.sharding import batch_axes
        bshard = NamedSharding(mesh, P(batch_axes(mesh, args.global_batch),
                                       None))

    t0 = time.time()
    for step in range(start_step, args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(pipe).items()}
        if bshard is not None:
            batch = {k: jax.device_put(v, bshard) for k, v in batch.items()}
        state, metrics = trainer.train_step(state, batch)
        if (step + 1) % args.log_every == 0 or step + 1 == args.steps:
            loss = float(metrics["loss"])
            gn = float(metrics["grad_norm"])
            tps = args.global_batch * args.seq_len * (step + 1 - start_step) \
                / max(time.time() - t0, 1e-9)
            print(f"step {step+1:5d}  loss {loss:.4f}  gnorm {gn:.3f}  "
                  f"tok/s {tps:,.0f}")
        if ckpt and ((step + 1) % args.ckpt_every == 0 or stop["flag"]
                     or step + 1 == args.steps):
            ckpt.save(state, step + 1,
                      extra={"step": step + 1, "data": pipe.state()})
        if stop["flag"]:
            print("preempted: final checkpoint committed")
            break
    if ckpt:
        ckpt.wait()
    return 0


if __name__ == "__main__":
    sys.exit(main())
