"""Decode attention: one query token vs a long KV cache, PUL-streamed.

The serving-side twin of pul_attention and the purest LM instance of the
paper's setting: a tiny amount of compute (one token's scores) against a
huge slow-memory operand (the KV cache), i.e. minimal operational intensity.
Each grid step handles one (batch, kv-head) pair; the cache streams through
a distance-d preload ring while the VPU reduces the previous block's online
softmax. All GQA query heads of the kv group ride the same stream (the
transfer is amortized over G heads — PUL's configurable transfer size).

Layout: q (B, H, hd); k/v caches (B, K, S, hd); `length` masks valid cache
entries (<= S), so ring/paged caches pass their fill level.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import (
    PULConfig, PreloadStream, pul_loop, ring_scratch, interpret_mode)

NEG_INF = -2.0e38


def _kernel(len_smem, q_vmem, k_hbm, v_hbm, o_vmem, kbuf, ksems, vbuf, vsems,
            *, cfg: PULConfig, bs: int, ns: int, S: int, group: int,
            scale: float, softcap: Optional[float]):
    b = pl.program_id(0)
    kv_h = pl.program_id(1)
    length = len_smem[b]

    k_st = PreloadStream(k_hbm, kbuf, ksems,
                         index_map=lambda t: (b, kv_h, t * bs, 0),
                         cfg=cfg, n_blocks=ns)
    v_st = PreloadStream(v_hbm, vbuf, vsems,
                         index_map=lambda t: (b, kv_h, t * bs, 0),
                         cfg=cfg, n_blocks=ns)

    q = q_vmem[0, 0].astype(jnp.float32)                # (G, hd)

    def body(t, views, carry):
        m, l, acc = carry                               # (G,1),(G,1),(G,hd)
        kt = views[0][0, 0].astype(jnp.float32)         # (bs, hd)
        vt = views[1][0, 0].astype(jnp.float32)
        logits = jnp.dot(q, kt.T, preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        jk = t * bs + jax.lax.iota(jnp.int32, bs)
        logits = jnp.where((jk < length)[None, :], logits, NEG_INF)  # (G,bs)
        bmax = jnp.max(logits, axis=-1, keepdims=True)
        new_m = jnp.maximum(m, bmax)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(logits - new_m)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.dot(p, vt, preferred_element_type=jnp.float32)
        return new_m, l, acc

    G, hd = q.shape
    init = (jnp.full((G, 1), NEG_INF, jnp.float32),
            jnp.zeros((G, 1), jnp.float32),
            jnp.zeros((G, hd), jnp.float32))
    m, l, acc = pul_loop(ns, [k_st, v_st], body, init, cfg)
    o_vmem[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_vmem.dtype)


def pul_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         length, *, cfg: PULConfig = PULConfig(),
                         bs: int = 128, scale: Optional[float] = None,
                         softcap: Optional[float] = None,
                         interpret: Optional[bool] = None) -> jax.Array:
    """q: (B,H,hd); k,v: (B,K,S,hd); length: (B,) valid cache entries.
    Returns (B,H,hd)."""
    B, H, hd = q.shape
    _, K, S, _ = k.shape
    assert H % K == 0
    G = H // K
    bs = min(bs, S)
    ns = -(-S // bs)
    if ns * bs != S:
        pad = ((0, 0), (0, 0), (0, ns * bs - S), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    length = jnp.asarray(length, jnp.int32).reshape(B)
    # group query heads by kv head: (B, K, G, hd)
    qg = q.reshape(B, K, G, hd)
    kern = functools.partial(_kernel, cfg=cfg, bs=bs, ns=ns, S=S, group=G,
                             scale=scale, softcap=softcap)
    out = pl.pallas_call(
        kern,
        grid=(B, K),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, hd), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h: (b, h, 0, 0)),
        scratch_shapes=[
            *ring_scratch(cfg, (1, 1, bs, hd), k.dtype),
            *ring_scratch(cfg, (1, 1, bs, hd), v.dtype),
        ],
        interpret=interpret_mode(interpret),
    )(length, qg, k, v)
    return out.reshape(B, H, hd)
