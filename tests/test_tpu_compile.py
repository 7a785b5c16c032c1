"""The serving kernels compile for a TPU v5e at published widths.

Interpret mode accepts layouts the chip's compiler refuses (slices that are
not tile-aligned, a 64-lane rope stream), so every CPU test can pass while
the chip cannot run the kernel. These tests compile with `interpret=False`
against a described (not attached) v5e, from shapes alone:

  * the GQA kernels at qwen3-1.7b widths (H=16, K=8, hd=128, 28 layers);
  * the MLA kernels at deepseek-v2-236b widths (H=128, kvr=512, dr=64);
  * the whole full-width qwen3-1.7b sweep decode step the engine jits.

Plane shapes come from `PackedKVLayout`, so the lane padding of the store
is part of what is compiled. The topology is described inside a fixture,
never at import, so every test worker collects the same tests.
"""
import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import PULConfig
from repro.kernels import (
    pul_paged_decode_attention,
    pul_paged_mla_decode_attention,
    pul_paged_sweep_decode_attention,
    pul_paged_sweep_mla_decode_attention,
)
from repro.models import build_model
from repro.serving import PackedKVLayout

B, P, MAX_SEQ = 8, 16, 2048
NF = B * (MAX_SEQ // P) + 4          # every slot resident + reserved frames
N_PAGES = MAX_SEQ // P
PUL = PULConfig(distance=4)
BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory on one described v5e chip; the persistent
    compilation cache is off meanwhile (a described chip's executables
    cannot be read back)."""
    one = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)


def _planes(arch, spec):
    """Abstract per-layer planes of `arch` at full width, as the pool
    allocates them."""
    cfg = dataclasses.replace(get_config(arch), paged_kv=True)
    layout = PackedKVLayout(cfg, B, MAX_SEQ)
    return cfg, [spec(layout.plane_shape(e, NF, P), BF16)
                 for e in layout.entries]


def _compile(fn, *args, **jit_kw):
    compiled = jax.jit(fn, **jit_kw).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_gqa_sweep_kernel_compiles(spec):
    cfg, (kp, vp) = _planes("qwen3-1.7b", spec)
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    assert kp.shape == (cfg.num_layers, NF, K, P, hd)
    vec = spec((B,), I32)
    _compile(functools.partial(pul_paged_sweep_decode_attention, cfg=PUL,
                               interpret=False),
             spec((B, H, hd), BF16), kp, vp, spec((), I32),
             spec((B, N_PAGES), I32), vec, spec((B, K, hd), BF16),
             spec((B, K, hd), BF16), vec, vec)


def test_gqa_per_layer_kernel_compiles(spec):
    cfg, (kp, _) = _planes("qwen3-1.7b", spec)
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pages = spec(kp.shape[1:], BF16)
    new = spec((B, K, hd), BF16)
    _compile(lambda q, k, v, pt, ln, kn, vn: pul_paged_decode_attention(
                 q, k, v, pt, ln, k_new=kn, v_new=vn, cfg=PUL,
                 interpret=False),
             spec((B, H, hd), BF16), pages, pages,
             spec((B, N_PAGES), I32), spec((B,), I32), new, new)


def test_mla_sweep_kernel_compiles(spec):
    cfg, planes = _planes("deepseek-v2-236b", spec)
    H, kvr, dr = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    cp, rp = (p for p in planes if p.shape[0] > 1)   # the scanned layers
    assert cp.shape[-1] == kvr and rp.shape[-1] == 128   # dr lane-padded
    vec = spec((B,), I32)
    _compile(functools.partial(pul_paged_sweep_mla_decode_attention,
                               scale=0.07, cfg=PUL, interpret=False),
             spec((B, H, kvr), BF16), spec((B, H, dr), BF16), cp, rp,
             spec((), I32), spec((B, N_PAGES), I32), vec,
             spec((B, kvr), BF16), spec((B, dr), BF16), vec, vec)


def test_mla_per_layer_kernel_compiles(spec):
    cfg, planes = _planes("deepseek-v2-236b", spec)
    H, kvr, dr = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    cp, rp = (spec(p.shape[1:], BF16) for p in planes if p.shape[0] > 1)
    _compile(functools.partial(pul_paged_mla_decode_attention, scale=0.07,
                               cfg=PUL, interpret=False),
             spec((B, H, kvr), BF16), spec((B, H, dr), BF16), cp, rp,
             spec((B, N_PAGES), I32), spec((B,), I32),
             spec((B, kvr), BF16), spec((B, dr), BF16))


def test_full_width_sweep_decode_step_compiles_in_place(spec, monkeypatch):
    """The engine's jitted single-sweep step for full qwen3-1.7b: the
    kernel is in the program and the donated planes are updated in place
    (no temporary as large as the store)."""
    # steer the kernels to Mosaic: this process's backend is the CPU
    kernels = importlib.import_module("repro.kernels.pul_attention")
    monkeypatch.setattr(kernels, "interpret_mode", lambda interpret=None: False)
    cfg, planes = _planes("qwen3-1.7b", spec)
    model = build_model(cfg)
    params = jax.tree.map(lambda s: spec(s.shape, s.dtype),
                          model.abstract_params())
    cache, _ = model.cache_specs(B, MAX_SEQ)
    # pageable leaves are placeholders on the sweep path (engine
    # `_sweep_cache_tree`); only idx is read
    tree = jax.tree.map(
        lambda s: spec((s.shape[0], 1) if s.ndim > 2 else s.shape, s.dtype),
        cache)
    layout = PackedKVLayout(cfg, B, MAX_SEQ)
    batch = {"tokens": spec((B, 1), I32), "pos0": spec((B,), I32),
             "page_table": spec((B, N_PAGES), I32),
             "frames": spec((B,), I32), "offsets": spec((B,), I32)}
    store = {e.plane_key: p for e, p in zip(layout.entries, planes)}
    compiled = _compile(
        functools.partial(model.paged_decode_step, pul_distance=4),
        params, batch, tree, store, donate_argnums=(3,))
    plane_bytes = sum(p.size * 2 for p in planes)
    assert compiled.memory_analysis().temp_size_in_bytes < plane_bytes / 8
