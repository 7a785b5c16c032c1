"""End-to-end behaviour: training convergence, accum equivalence, pipeline
emitter invariants, dry-run machinery on a tiny mesh (subprocess)."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.optim import OptimizerConfig, adamw_init
from repro.data import DataConfig, TokenPipeline

ROOT = Path(__file__).resolve().parent.parent


def test_training_loss_decreases():
    """A tiny model must overfit a repeated batch quickly."""
    cfg = get_config("qwen3-1.7b").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    step_fn = jax.jit(make_train_step(
        cfg, OptimizerConfig(lr=3e-3, warmup_steps=2, total_steps=40)))
    data = TokenPipeline(DataConfig(global_batch=4, seq_len=32,
                                    vocab_size=cfg.vocab_size, seed=0))
    batch = next(data)
    losses = []
    for _ in range(30):
        params, opt, metrics = step_fn(params, opt, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 1.0, losses[::6]
    assert np.isfinite(losses).all()


def test_grad_accum_equivalent_to_full_batch():
    """accum=4 over a batch == accum=1 (same grads => same update)."""
    cfg = get_config("musicgen-large").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    data = TokenPipeline(DataConfig(global_batch=8, seq_len=16,
                                    vocab_size=cfg.vocab_size, seed=2,
                                    frontend_tokens=cfg.frontend_tokens,
                                    d_model=cfg.d_model))
    batch = next(data)
    ocfg = OptimizerConfig(lr=1e-3, warmup_steps=0)
    p1, _, m1 = jax.jit(make_train_step(cfg, ocfg, accum=1))(
        params, adamw_init(params), batch)
    p4, _, m4 = jax.jit(make_train_step(cfg, ocfg, accum=4))(
        params, adamw_init(params), batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]), rtol=2e-2)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=3e-2)


def test_trainer_cli_runs_and_resumes(tmp_path):
    """The real launcher: run 6 steps, kill, rerun -> resumes from ckpt."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    args = [sys.executable, "-m", "repro.launch.train", "--arch", "qwen3-1.7b",
            "--reduced", "--steps", "6", "--batch", "2", "--seq", "16",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "3",
            "--log-every", "2"]
    out1 = subprocess.run(args[:10] + ["--ckpt-dir", str(tmp_path),
                                       "--ckpt-every", "3", "--log-every", "2"],
                          env=env, cwd=ROOT, capture_output=True,
                          text=True, timeout=600)
    assert out1.returncode == 0, out1.stderr[-2000:]
    out2 = subprocess.run(args, env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert out2.returncode == 0, out2.stderr[-2000:]
    assert "resumed from step" in out2.stdout


def test_dryrun_collective_parser():
    from repro.launch.dryrun import collective_bytes_from_hlo
    hlo = """
  %ag = bf16[256,4096]{1,0} all-gather(%x), replica_groups=[32,16]<=[512], dimensions={0}
  %ar = f32[1024]{0} all-reduce(%y), replica_groups=[2,256]<=[512]
  %agd = bf16[8]{0} all-gather-done(%ag)
  %cp = bf16[128,128]{1,0} collective-permute(%z), source_target_pairs={{0,1}}
"""
    total, kinds, by_depth = collective_bytes_from_hlo(hlo, 512)
    ag = 256 * 4096 * 2 * 15 / 16
    ar = 1024 * 4 * 2 * 255 / 256
    cp = 128 * 128 * 2
    assert kinds["all-gather"] == int(ag)
    assert kinds["all-reduce"] == int(ar)
    assert kinds["collective-permute"] == int(cp)
    assert total == int(ag) + int(ar) + int(cp)
    assert by_depth == {0: int(ag) + int(ar) + int(cp)}


def test_dryrun_tiny_mesh_subprocess():
    """Real lower+compile of a reduced arch on a forced 4-device host mesh
    (exercises the same cell_specs/shardings path as the 512-dev dry-run)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from repro.configs import get_config, SHAPES
from repro.launch import steps as S
from repro.models import module as M
import dataclasses
cfg = get_config("gemma2-27b").reduced()
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
with jax.set_mesh(mesh):
    fn = S.make_train_step(cfg, accum=2)
    from repro.models import zoo
    model = zoo.build_model(cfg)
    aparams = model.abstract_params()
    pspecs = M.param_specs(model.params, mesh)
    opt = {"m": jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), aparams),
           "v": jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), aparams),
           "step": jax.ShapeDtypeStruct((), jnp.int32)}
    ospecs = {"m": pspecs, "v": pspecs, "step": jax.sharding.PartitionSpec()}
    batch = {"tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32),
             "targets": jax.ShapeDtypeStruct((4, 32), jnp.int32),
             "loss_mask": jax.ShapeDtypeStruct((4, 32), jnp.float32)}
    bspecs = {"tokens": jax.sharding.PartitionSpec("data"),
              "targets": jax.sharding.PartitionSpec("data"),
              "loss_mask": jax.sharding.PartitionSpec("data")}
    P = jax.sharding.PartitionSpec
    NS = jax.sharding.NamedSharding
    to_shard = lambda tree: jax.tree.map(lambda s: NS(mesh, s), tree,
                                         is_leaf=lambda x: isinstance(x, P))
    compiled = jax.jit(fn, in_shardings=(
        to_shard(pspecs), to_shard(ospecs), to_shard(bspecs))).lower(
        aparams, opt, batch).compile()
    assert compiled.cost_analysis()["flops"] > 0
    print("TINY_DRYRUN_OK", int(compiled.memory_analysis().temp_size_in_bytes))
"""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "TINY_DRYRUN_OK" in out.stdout
