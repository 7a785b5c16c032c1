"""Substrate: sharding resolver, optimizer, compression, data, checkpoint,
fault handling, serving engine."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs import CONFIGS, get_config
from repro.checkpoint import CheckpointConfig, CheckpointManager
from repro.data import DataConfig, TokenPipeline
from repro.models import build_model, demo_batch
from repro.models import module as M
from repro.optim import OptimizerConfig, adamw_init, adamw_update, cosine_schedule
from repro.optim import compression as C
from repro.runtime.fault import HeartbeatMonitor, rescale_plan
from repro.runtime.sharding import ShardingRules, logical_to_spec
from repro.serving import EngineConfig, Request, ServingEngine


# ---------------------------------------------------------------- sharding
class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape


MESH_SINGLE = _FakeMesh({"data": 16, "model": 16})
MESH_MULTI = _FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_resolver_basic_2d_weight():
    spec = logical_to_spec(("embed", "ff"), (4096, 16384), MESH_MULTI)
    assert spec == P(("pod", "data"), "model")


def test_resolver_divisibility_fallback():
    # 40 heads don't divide the 16-way model axis -> replicated
    spec = logical_to_spec(("embed", "heads", "head_dim"), (5120, 40, 128),
                           MESH_MULTI)
    assert spec == P(("pod", "data"))
    # 48 heads do
    spec = logical_to_spec(("embed", "heads", "head_dim"), (6144, 48, 128),
                           MESH_MULTI)
    assert spec == P(("pod", "data"), "model")


def test_resolver_no_axis_reuse():
    # batch takes (pod,data); cache_seq then falls to model
    spec = logical_to_spec(("cache_batch", "cache_seq", "act_kv_heads", None),
                           (128, 32768, 8, 128), MESH_MULTI)
    assert spec == P(("pod", "data"), "model")


def test_resolver_single_pod_mesh_skips_pod_axis():
    spec = logical_to_spec(("embed", "ff"), (4096, 16384), MESH_SINGLE)
    assert spec == P("data", "model")


def test_resolver_every_param_of_every_arch(subtests=None):
    """No Param in the zoo fails to resolve on either mesh."""
    for mesh in (MESH_SINGLE, MESH_MULTI):
        for arch, cfg in CONFIGS.items():
            tree = build_model(cfg).params
            specs = M.param_specs(tree, mesh)      # raises on failure
            assert len(jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))) > 0


# --------------------------------------------------------------- optimizer
def test_adamw_matches_reference_step():
    params = {"w": jnp.ones((4, 4), jnp.bfloat16), "b": jnp.zeros((4,), jnp.float32)}
    grads = {"w": jnp.full((4, 4), 0.5, jnp.float32), "b": jnp.ones((4,), jnp.float32)}
    cfg = OptimizerConfig(lr=1e-2, warmup_steps=0, total_steps=10,
                          weight_decay=0.0, clip_norm=1e9)
    state = adamw_init(params)
    new_p, new_s, metrics = adamw_update(grads, state, params, cfg)
    # step 1: mhat = g, vhat = g^2 -> delta = g/|g| = 1
    lr1 = float(cosine_schedule(cfg, jnp.int32(1)))
    np.testing.assert_allclose(np.asarray(new_p["b"]),
                               -lr1 * np.ones(4), rtol=1e-4)
    assert int(metrics["step"]) == 1


def test_grad_clipping():
    params = {"w": jnp.zeros((8,), jnp.float32)}
    big = {"w": jnp.full((8,), 100.0)}
    cfg = OptimizerConfig(lr=1.0, clip_norm=1.0, warmup_steps=0,
                          weight_decay=0.0)
    state = adamw_init(params)
    _, _, metrics = adamw_update(big, state, params, cfg)
    assert float(metrics["grad_norm"]) > 100


# -------------------------------------------------------------- compression
@settings(max_examples=30, deadline=None)
@given(st.integers(0, 1000))
def test_quantize_roundtrip_bounded(seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (64,)) * 10
    q, s = C.quantize_int8(x)
    err = np.abs(np.asarray(C.dequantize_int8(q, s) - x))
    assert err.max() <= float(s) / 2 + 1e-6


def test_error_feedback_reduces_bias():
    """With error feedback, the *cumulative* quantized sum tracks the true
    cumulative sum much better than independent quantization."""
    g = jax.random.normal(jax.random.PRNGKey(0), (256,)) * 0.01
    err = jnp.zeros_like(g)
    acc_ef, acc_naive = jnp.zeros_like(g), jnp.zeros_like(g)
    for _ in range(50):
        q, s, err = C.ef_quantize(g, err)
        acc_ef += C.dequantize_int8(q, s)
        qn, sn = C.quantize_int8(g)
        acc_naive += C.dequantize_int8(qn, sn)
    true = g * 50
    assert (jnp.linalg.norm(acc_ef - true)
            <= jnp.linalg.norm(acc_naive - true) + 1e-5)


# --------------------------------------------------------------------- data
def test_data_deterministic_and_resumable():
    cfg = DataConfig(global_batch=4, seq_len=32, vocab_size=1000, seed=7)
    p1 = TokenPipeline(cfg)
    batches = [next(p1) for _ in range(5)]
    p2 = TokenPipeline(cfg)
    p2.skip_to(3)
    b3 = next(p2)
    np.testing.assert_array_equal(np.asarray(batches[3]["tokens"]),
                                  np.asarray(b3["tokens"]))


def test_data_prefetch_thread_matches_sync():
    cfg = DataConfig(global_batch=2, seq_len=16, vocab_size=100, seed=1,
                     prefetch_distance=3)
    sync = TokenPipeline(cfg)
    want = [np.asarray(next(sync)["tokens"]) for _ in range(4)]
    pre = TokenPipeline(cfg)
    pre.start()
    got = [np.asarray(next(pre)["tokens"]) for _ in range(4)]
    pre.stop()
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)


def test_data_targets_are_shifted_tokens():
    cfg = DataConfig(global_batch=2, seq_len=16, vocab_size=50, seed=3)
    b = next(TokenPipeline(cfg))
    # targets[t] == token stream at t+1 (teacher forcing) — checked via
    # overlap: tokens[1:] == targets[:-1]
    np.testing.assert_array_equal(np.asarray(b["tokens"][:, 1:]),
                                  np.asarray(b["targets"][:, :-1]))


# --------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip_and_gc():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(CheckpointConfig(d, keep=2))
        state = {"w": jnp.arange(8, dtype=jnp.float32),
                 "n": {"v": jnp.ones((2, 2), jnp.bfloat16)}}
        for s in (10, 20, 30):
            mgr.save(s, jax.tree.map(lambda x: x * s, state))
        mgr.wait()
        assert mgr.latest_step() == 30
        step, restored = mgr.restore(like=state)
        assert step == 30
        np.testing.assert_allclose(np.asarray(restored["w"], np.float32),
                                   np.arange(8) * 30)
        # keep=2 garbage-collected step 10
        assert mgr._steps() == [20, 30]


def test_checkpoint_restart_continuation():
    """Kill-and-restart yields the same state as an uninterrupted run."""
    cfg = get_config("qwen3-1.7b").reduced()
    m = build_model(cfg)
    from repro.launch.steps import make_train_step
    step_fn = jax.jit(make_train_step(cfg, OptimizerConfig(lr=1e-3)))
    dcfg = DataConfig(global_batch=2, seq_len=16, vocab_size=cfg.vocab_size,
                      seed=5)

    def run(n_steps, params, opt, start=0):
        data = TokenPipeline(dcfg)
        data.skip_to(start)
        for _ in range(start, n_steps):
            params, opt, _ = step_fn(params, opt, next(data))
        return params, opt

    p0 = m.init(jax.random.PRNGKey(0))
    o0 = adamw_init(p0)
    p_full, o_full = run(4, p0, o0)

    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(CheckpointConfig(d))
        p2, o2 = run(2, p0, o0)
        mgr.save(2, (p2, o2), block=True)
        step, (p2r, o2r) = mgr.restore(like=(p2, o2))
        p_resumed, _ = run(4, p2r, o2r, start=step)
    for a, b in zip(jax.tree.leaves(p_full), jax.tree.leaves(p_resumed)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)


# -------------------------------------------------------------------- fault
def test_heartbeat_dead_worker_detection():
    hb = HeartbeatMonitor(deadline_s=10)
    hb.beat("a", now=0.0)
    hb.beat("b", now=0.0)
    hb.beat("a", now=8.0)
    assert hb.dead_workers(now=12.0) == ["b"]


def test_straggler_detection():
    hb = HeartbeatMonitor()
    for i in range(16):
        for w in ("a", "b", "c", "d"):
            hb.beat(w, step_time=1.0 + (3.0 if w == "c" else 0.0))
    assert hb.stragglers() == ["c"]


def test_rescale_plan():
    plan = rescale_plan(2, 1)
    assert plan.new_mesh == (16, 16)
    assert plan.batch_scale == 2.0
    plan = rescale_plan(1, 2)
    assert plan.new_mesh == (2, 16, 16)
    with pytest.raises(ValueError):
        rescale_plan(2, 0)


# ------------------------------------------------------------------ serving
def test_serving_engine_matches_manual_decode():
    cfg = get_config("qwen3-1.7b").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, EngineConfig(batch_slots=2, max_seq=64,
                                                  prefill_bucket=16))
    prompt = [5, 7, 11, 13]
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=4))
    out = eng.run()[0]
    assert len(out) == 4

    # manual greedy decode with the prompt right-padded to the engine's
    # bucket: logits at the last real token, cache fill level = length
    bucket = 16
    toks = np.zeros((2, bucket), np.int32)
    toks[0, :len(prompt)] = prompt
    lengths = jnp.asarray([len(prompt), 1], jnp.int32)
    logits, caches = jax.jit(lambda p, b: m.prefill(p, b, max_seq=64))(
        params, {"tokens": jnp.asarray(toks), "lengths": lengths})
    caches = jax.tree_util.tree_map_with_path(
        lambda path, x: (jnp.broadcast_to(lengths, x.shape)
                         if getattr(path[-1], "key", None) == "idx" else x),
        caches)
    manual = [int(np.argmax(np.asarray(logits)[0]))]
    pos = lengths
    for _ in range(3):
        step = np.zeros((2, 1), np.int32)
        step[0, 0] = manual[-1]
        logits, caches = jax.jit(m.decode_step)(
            params, {"tokens": jnp.asarray(step), "pos0": pos}, caches)
        manual.append(int(np.argmax(np.asarray(logits)[0])))
        pos = pos + 1
    assert out == manual
