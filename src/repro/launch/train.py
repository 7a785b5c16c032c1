"""Production trainer: mesh + sharded state + PUL data pipeline + async
checkpointing + fault-tolerant restart.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
      --steps 200 --batch 8 --seq 128 --reduced --ckpt-dir /tmp/ckpt

`--reduced` runs the smoke-size config on local devices (CPU-friendly);
full-size runs expect a real TPU slice (same code path, bigger mesh).
Restart semantics: rerunning the same command resumes from the latest
committed checkpoint and skips the data stream to the restored step.
"""
from __future__ import annotations

import argparse
import time
from typing import List

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointConfig, CheckpointManager
from repro.configs import get_config
from repro.data import DataConfig, TokenPipeline
from repro.launch import steps as S
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import zoo
from repro.optim import OptimizerConfig, adamw_init
from repro.runtime.fault import HeartbeatMonitor


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default=None,
                    help="e.g. 2x4 => (data=2, model=4) over local devices")
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


def model_config(args):
    cfg = get_config(args.arch)
    return cfg.reduced() if args.reduced else cfg


def mesh_of(args):
    """(data, model) mesh over the local devices: `--mesh DxM`, or all of
    them on the data axis."""
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        return make_mesh((d, m), ("data", "model"))
    return make_mesh((jax.device_count(), 1), ("data", "model"))


def data_pipeline(cfg, args) -> TokenPipeline:
    return TokenPipeline(DataConfig(
        global_batch=args.batch, seq_len=args.seq,
        vocab_size=cfg.vocab_size, frontend_tokens=cfg.frontend_tokens,
        d_model=cfg.d_model, prefetch_distance=2))


def init_state(cfg, mesh):
    """Parameters (seeded) and AdamW state, both placed by the sharding
    rules. The moments need explicit out_shardings: zeros depend on no
    input, so the partitioner would replicate them on every device. Call
    inside `jax.set_mesh(mesh)`."""
    model = zoo.build_model(cfg)
    pspecs, ospecs = S.state_specs(cfg, mesh)
    place = lambda specs: jax.tree.map(lambda s: NamedSharding(mesh, s),
                                       specs, is_leaf=lambda x: isinstance(x, P))
    params = jax.jit(model.init, out_shardings=place(pspecs))(
        jax.random.PRNGKey(0))
    mdt = jnp.bfloat16 if cfg.bf16_moments else jnp.float32
    opt_state = jax.jit(lambda p: adamw_init(p, mdt),
                        out_shardings=place(ospecs))(params)
    return params, opt_state


def train(cfg, args, params, opt_state) -> List[float]:
    """Run steps up to `args.steps` (resuming from `--ckpt-dir` when it
    holds a checkpoint); returns the loss of every step run. Call inside
    `jax.set_mesh(mesh)`."""
    opt_cfg = OptimizerConfig(lr=args.lr, total_steps=args.steps,
                              warmup_steps=max(1, args.steps // 20))
    train_step = S.make_train_step(cfg, opt_cfg, accum=args.accum)
    data = data_pipeline(cfg, args)
    start = 0
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(CheckpointConfig(args.ckpt_dir))
        if mgr.latest_step() is not None:
            start, (params, opt_state) = mgr.restore(like=(params, opt_state))
            print(f"[train] resumed from step {start}")
    data.skip_to(start)
    data.start()

    jstep = jax.jit(train_step, donate_argnums=(0, 1))
    hb = HeartbeatMonitor()
    t_last = time.time()
    losses = []
    for step in range(start, args.steps):
        batch = next(data)
        params, opt_state, metrics = jstep(params, opt_state, batch)
        losses.append(metrics["loss"])
        if (step + 1) % args.log_every == 0 or step == start:
            loss = float(metrics["loss"])
            dt = time.time() - t_last
            t_last = time.time()
            hb.beat("worker0", dt)
            print(f"[train] step {step + 1} loss {loss:.4f} "
                  f"({dt / args.log_every:.3f}s/step)")
        if mgr and (step + 1) % args.ckpt_every == 0:
            mgr.save(step + 1, (params, opt_state))   # async unload
    if mgr:
        mgr.save(args.steps, (params, opt_state), block=True)
    data.stop()
    return [float(x) for x in losses]


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    cfg = model_config(args)
    mesh = mesh_of(args)
    with jax.set_mesh(mesh):
        params, opt_state = init_state(cfg, mesh)
        losses = train(cfg, args, params, opt_state)
    print("[train] done; final loss", losses[-1] if losses else "n/a")


if __name__ == "__main__":
    main()
