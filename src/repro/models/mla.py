"""Multi-head Latent Attention (DeepSeek-V2).

Train/prefill run the standard decompressed path. Decode runs the *absorbed*
path (q-side absorption of the k up-projection, output-side absorption of the
v up-projection), attending directly over the compressed (c_kv, k_rope) cache
— this is what makes a 524k-token-free... rather, 32k x 128-batch decode
feasible: the cache holds (kv_lora + rope) = 576 dims per token instead of
n_heads*(192+128).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import NEG_INF, rms_norm, rms_norm_params, rope
from repro.models.module import Param
from repro.runtime.sharding import constrain


def mla_params(cfg: ModelConfig) -> Dict[str, Any]:
    D, H = cfg.d_model, cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = jnp.bfloat16
    return {
        "wq_a": Param((D, qr), ("embed", "q_rank"), dt, "fan_in"),
        "q_norm": rms_norm_params(qr),
        "wq_b": Param((qr, H, dn + dr), ("q_rank", "heads", "head_dim"), dt, "fan_in"),
        "wkv_a": Param((D, kvr + dr), ("embed", "kv_rank"), dt, "fan_in"),
        "kv_norm": rms_norm_params(kvr),
        "wkv_b": Param((kvr, H, dn + dv), ("kv_rank", "heads", "head_dim"), dt, "fan_in"),
        "wo": Param((H, dv, D), ("heads", "head_dim", "embed"), dt, "fan_in"),
    }


def _project_q(p, x, cfg: ModelConfig, positions):
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q_c = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("btr,rhk->bthk", q_c, p["wq_b"])
    q = constrain(q, ("batch", None, "act_heads", None))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _compress_kv(p, x, cfg: ModelConfig, positions):
    kvr, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    kv = x @ p["wkv_a"]
    c_kv = rms_norm(kv[..., :kvr], p["kv_norm"], cfg.norm_eps)
    k_rope = rope(kv[..., kvr:][:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    return c_kv, k_rope


def mla_apply(
    p,
    x,
    *,
    cfg: ModelConfig,
    positions,
    kind: str,
    cache: Optional[Dict[str, Any]] = None,
    max_seq: Optional[int] = None,
    paged=None,             # paged_decode: a layers.PagedSweep
):
    """Returns (y, new_cache). Cache: {"c_kv": (B,Smax,kvr), "k_rope":
    (B,Smax,dr), "idx": ()} — compressed, per the MLA design.

    kind="paged_decode" reads the compressed KV from the page planes in
    `paged` (a `layers.PagedSweep`; the cache's "c_kv"/"k_rope" are
    placeholders, "idx" the (B,) per-slot fill): the absorbed attention runs
    straight over the pages (kernels.pul_paged_sweep_mla_decode_attention),
    whose epilogue commits the current token's compressed rows; the returned
    cache holds those rows."""
    B, T, D = x.shape
    H = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    scale = 1.0 / math.sqrt(dn + dr)
    q_nope, q_rope = _project_q(p, x, cfg, positions)

    if kind == "paged_decode":
        assert T == 1, "paged decode processes one token per step"
        from repro.kernels.pul_attention import (
            pul_paged_sweep_mla_decode_attention)
        idx = jnp.asarray(cache["idx"], jnp.int32).reshape(B)
        c_new, r_new = _compress_kv(p, x, cfg, positions)
        wkv_b_k = p["wkv_b"][..., :dn]                      # (kvr, H, dn)
        wkv_b_v = p["wkv_b"][..., dn:]                      # (kvr, H, dv)
        q_abs = jnp.einsum("bthn,rhn->bthr", q_nope, wkv_b_k)[:, 0]
        # one sweep over the full per-layer compressed planes; the fused
        # epilogue commits c_new/r_new to the tail page
        cp, rp = paged.plane("c_kv"), paged.plane("k_rope")
        c_new = c_new[:, 0].astype(cp.dtype)
        r_new = r_new[:, 0].astype(rp.dtype)
        o_c, cp, rp = pul_paged_sweep_mla_decode_attention(
            q_abs, q_rope[:, 0], cp, rp, paged.layer, paged.page_table,
            idx, c_new, r_new, paged.frames, paged.offsets, scale=scale,
            cfg=paged.pul_cfg)
        paged.set_plane("c_kv", cp)
        paged.set_plane("k_rope", rp)
        out = jnp.einsum("bhr,rhv->bhv", o_c, wkv_b_v)[:, None]
        new_cache = {"c_kv": c_new, "k_rope": r_new, "idx": idx + 1}
    elif kind == "decode":
        # Per-slot fill levels (idx: (B,)) — see layers.attention_apply.
        assert T == 1, "decode processes one token per step"
        idx = jnp.broadcast_to(jnp.asarray(cache["idx"], jnp.int32), (B,))
        c_new, r_new = _compress_kv(p, x, cfg, positions)
        rows = jnp.arange(B)
        S = cache["c_kv"].shape[1]
        write = jax.lax.rem(idx, S)
        c_kv = cache["c_kv"].at[rows, write].set(
            c_new[:, 0].astype(cache["c_kv"].dtype))
        k_rope = cache["k_rope"].at[rows, write].set(
            r_new[:, 0].astype(cache["k_rope"].dtype))
        # absorbed path: q into compressed space; attend over (c_kv, k_rope)
        wkv_b_k = p["wkv_b"][..., :dn]                      # (kvr, H, dn)
        wkv_b_v = p["wkv_b"][..., dn:]                      # (kvr, H, dv)
        q_abs = jnp.einsum("bthn,rhn->bthr", q_nope, wkv_b_k)   # (B,T,H,kvr)
        logits = (
            jnp.einsum("bthr,bsr->bhts", q_abs, c_kv)
            + jnp.einsum("bthn,bsn->bhts", q_rope, k_rope)
        ).astype(jnp.float32) * scale
        mask = (jnp.arange(S)[None, None, None, :]
                <= idx[:, None, None, None])
        logits = jnp.where(mask, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(c_kv.dtype)
        o_c = jnp.einsum("bhts,bsr->bthr", probs, c_kv)     # (B,T,H,kvr)
        out = jnp.einsum("bthr,rhv->bthv", o_c, wkv_b_v)    # (B,T,H,dv)
        new_cache = {"c_kv": c_kv, "k_rope": k_rope, "idx": idx + 1}
    else:
        c_kv, k_rope = _compress_kv(p, x, cfg, positions)
        kv = jnp.einsum("btr,rhk->bthk", c_kv, p["wkv_b"])
        kv = constrain(kv, ("batch", None, "act_heads", None))
        k_nope, v = kv[..., :dn], kv[..., dn:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, T, H, dr))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        from repro.models.layers import KV_BLOCK, _attend_chunked
        if T > 2 * KV_BLOCK:
            # nope/rope head dims differ from v head dim; the streaming core
            # only needs matching q/k dims, v dim is free
            out = _attend_chunked(q, k, v, softcap=None, scale=scale,
                                  window=None)
        else:
            logits = jnp.einsum("bthk,bshk->bhts", q, k).astype(jnp.float32) * scale
            mask = (jnp.arange(T)[:, None] >= jnp.arange(T)[None, :])[None, None]
            logits = jnp.where(mask, logits, NEG_INF)
            probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
            out = jnp.einsum("bhts,bshv->bthv", probs, v)
        new_cache = None
        if kind == "prefill":
            target = max_seq or T
            ckv_c, kr_c = c_kv, k_rope
            if target > T:
                ckv_c = jnp.pad(c_kv, ((0, 0), (0, target - T), (0, 0)))
                kr_c = jnp.pad(k_rope, ((0, 0), (0, target - T), (0, 0)))
            new_cache = {"c_kv": ckv_c.astype(jnp.bfloat16),
                         "k_rope": kr_c.astype(jnp.bfloat16),
                         "idx": jnp.full((B,), T, jnp.int32)}
    y = jnp.einsum("bthv,hvd->btd", out, p["wo"])
    return y, new_cache


def mla_cache_spec(cfg: ModelConfig, batch: int, max_seq: int):
    return {
        "c_kv": jax.ShapeDtypeStruct((batch, max_seq, cfg.kv_lora_rank), jnp.bfloat16),
        "k_rope": jax.ShapeDtypeStruct((batch, max_seq, cfg.qk_rope_head_dim), jnp.bfloat16),
        "idx": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }


def mla_cache_logical():
    return {
        "c_kv": ("cache_batch", "cache_seq", "kv_rank"),
        "k_rope": ("cache_batch", "cache_seq", "kv_rank"),
        "idx": ("cache_batch",),
    }
