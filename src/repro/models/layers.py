"""Shared transformer layers: norms, RoPE, GQA attention, MLPs, embeddings.

All layers are (param-tree builder, pure apply fn) pairs. Attention supports
the zoo's flavors: GQA grouping, per-head qk RMS-norm (qwen3/gemma3), QKV
bias (qwen2.5), attention-logit softcap (gemma2/grok), sliding-window local
layers (gemma2/3), and an incremental KV-cache decode path.

Compute dtype is bf16 with fp32 softmax/norm internals.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.module import Param
from repro.runtime.sharding import constrain as _constrain

NEG_INF = -2.0e38  # large finite; avoids nan from (-inf) - (-inf)


class PagedSweep:
    """Routing context for the single-sweep paged decode.

    The engine serves decode from per-layer page planes (`KVStoreLayout`
    v2); `paged` carries one of these. The backbone threads the FULL
    stacked planes through the layer scan's carry and mutates this context
    as tracing walks the blocks: it sets `prefix` to the current block's
    cache path and `layer` to the scan-carried group index; each attention
    block then reads its planes via :meth:`plane`, calls the sweep kernel
    (which selects the layer in-kernel from an SMEM scalar and commits the
    current token's rows in its fused epilogue), and writes the aliased
    plane outputs back via :meth:`set_plane`. `frames`/`offsets` ((B,)
    int32) name each slot's tail-page commit position (TRASH frame for
    inactive slots).
    """

    def __init__(self, page_table, pul_cfg, frames, offsets, planes):
        self.page_table = page_table
        self.pul_cfg = pul_cfg
        self.frames = frames
        self.offsets = offsets
        self.planes = planes        # {plane_key: (L, NF, ...) full plane}
        self.prefix: Tuple[str, ...] = ()
        self.layer = 0              # traced group index inside the scan

    def _key(self, leaf: str) -> str:
        return "/".join((*self.prefix, leaf))

    def plane(self, leaf: str):
        return self.planes[self._key(leaf)]

    def set_plane(self, leaf: str, value) -> None:
        self.planes[self._key(leaf)] = value


# --------------------------------------------------------------------------
# norms / positions
# --------------------------------------------------------------------------
def rms_norm_params(dim: int, name_axis: str = "norm") -> Param:
    return Param((dim,), (name_axis,), dtype=jnp.float32, init="zeros")


def rms_norm(x, scale, eps: float = 1e-6):
    """RMSNorm with gemma-style (1 + scale) parameterization (zero init)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def rope(x, positions, theta: float):
    """Half-split rotary embedding. x: (..., seq, n, head_dim), positions
    broadcastable to (..., seq)."""
    dt = x.dtype
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None, None] * freqs  # (..., S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half : 2 * half].astype(jnp.float32)
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    if hd != 2 * half:  # odd head_dim (zamba 112 is even; guard anyway)
        rot = jnp.concatenate([rot, x[..., 2 * half :].astype(jnp.float32)], axis=-1)
    return rot.astype(dt)


def sinusoidal_embedding(positions, dim: int, max_scale: float = 1e4):
    """Absolute sinusoidal position embedding (musicgen). positions: (B,S)."""
    half = dim // 2
    freqs = max_scale ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------
def attention_params(cfg: ModelConfig) -> Dict[str, Any]:
    D, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = jnp.bfloat16
    p: Dict[str, Any] = {
        "wq": Param((D, H, hd), ("embed", "heads", "head_dim"), dt, "fan_in"),
        "wk": Param((D, K, hd), ("embed", "kv_heads", "head_dim"), dt, "fan_in"),
        "wv": Param((D, K, hd), ("embed", "kv_heads", "head_dim"), dt, "fan_in"),
        "wo": Param((H, hd, D), ("heads", "head_dim", "embed"), dt, "fan_in"),
    }
    if cfg.qkv_bias:
        p["bq"] = Param((H, hd), ("heads", "head_dim"), dt, "zeros")
        p["bk"] = Param((K, hd), ("kv_heads", "head_dim"), dt, "zeros")
        p["bv"] = Param((K, hd), ("kv_heads", "head_dim"), dt, "zeros")
    if cfg.qk_norm:
        p["q_norm"] = rms_norm_params(hd)
        p["k_norm"] = rms_norm_params(hd)
    return p


def _attend(q, k, v, *, mask, softcap: Optional[float], scale: float):
    """q: (B,T,H,hd) k/v: (B,S,K,hd); grouped-query attention core."""
    B, T, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    q = q.reshape(B, T, K, G, hd)
    logits = jnp.einsum("btkgh,bskh->bkgts", q, k).astype(jnp.float32) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskh->btkgh", probs, v)
    return out.reshape(B, T, H, hd)


# KV-block length for the streaming (flash-style) attention path. Sequences
# longer than 2*KV_BLOCK never materialize (T, S) scores — the XLA-level
# analogue of kernels/pul_attention.py (which is the TPU-optimal realization
# of the same schedule: preload KV tiles, online softmax, unload out-tiles).
KV_BLOCK = 1024


def _attend_chunked(q, k, v, *, softcap: Optional[float], scale: float,
                    window: Optional[int], kv_block: int = KV_BLOCK):
    """Causal GQA attention, lax.scan over KV blocks with online softmax.

    Math-identical to `_attend` with a causal (+optional sliding window)
    mask; peak memory is O(T * kv_block) per head instead of O(T * S)."""
    B, T, H, hd = q.shape
    S = k.shape[1]
    K = k.shape[2]
    vd = v.shape[-1]                                           # may differ (MLA)
    G = H // K
    nb = -(-S // kv_block)
    pad = nb * kv_block - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qg = q.reshape(B, T, K, G, hd)
    kb = k.reshape(B, nb, kv_block, K, hd).swapaxes(0, 1)     # (nb,B,kb,K,hd)
    vb = v.reshape(B, nb, kv_block, K, vd).swapaxes(0, 1)
    offs = jnp.arange(nb, dtype=jnp.int32) * kv_block
    iq = jnp.arange(T)                                         # absolute = iq (T==S)

    def body(carry, inp):
        m, l, acc = carry
        kc, vc, off = inp
        logits = jnp.einsum("btkgh,bskh->bkgts", qg, kc).astype(jnp.float32) * scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        jk = off + jnp.arange(kv_block)
        msk = (jk[None, :] <= iq[:, None]) & (jk[None, :] < S)
        if window is not None:
            msk &= jk[None, :] > iq[:, None] - window
        logits = jnp.where(msk[None, None, None], logits, NEG_INF)
        bmax = jnp.max(logits, axis=-1)
        new_m = jnp.maximum(m, bmax)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(logits - new_m[..., None])
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bkgts,bskh->bkgth", p, vc)
        return (new_m, l, acc), ()

    m0 = jnp.full((B, K, G, T), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, T), jnp.float32)
    a0 = jnp.zeros((B, K, G, T, vd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, offs))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.swapaxes(2, 3).swapaxes(1, 2).reshape(B, T, H, vd).astype(v.dtype)


def _causal_mask(tq: int, tk: int, *, offset: int, window: Optional[int]):
    """(1,1,1,tq,tk) boolean mask. `offset` = absolute position of query 0
    minus absolute position of key 0 (decode: cache_len-1)."""
    iq = jnp.arange(tq)[:, None] + offset
    jk = jnp.arange(tk)[None, :]
    m = jk <= iq
    if window is not None:
        m &= jk > iq - window
    return m[None, None, None]


def attention_apply(
    p,
    x,
    *,
    cfg: ModelConfig,
    positions,
    kind: str,              # "train" | "prefill" | "decode" | "paged_decode"
    local: bool = False,
    cache: Optional[Dict[str, Any]] = None,
    max_seq: Optional[int] = None,  # prefill: emit caches sized for decode
    paged: Optional[PagedSweep] = None,  # paged_decode: planes + routing
):
    """Returns (y, new_cache). Cache: {"k","v": (B,Smax,K,hd), "idx": ()}.

    kind="paged_decode" reads the KV from the page planes in `paged`
    instead (the cache's "k"/"v" are placeholders; "idx" is the (B,)
    per-slot fill): attention streams straight over the pages
    (kernels.pul_paged_sweep_decode_attention), whose epilogue commits the
    current token's rows; the returned cache holds those rows."""
    B, T, D = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = _constrain(q, ("batch", None, "act_heads", None))
    k = _constrain(k, ("batch", None, "act_kv_heads", None))
    v = _constrain(v, ("batch", None, "act_kv_heads", None))
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    theta = cfg.rope_theta
    if local and cfg.local_rope_theta is not None:
        theta = cfg.local_rope_theta
    if cfg.pos_embedding == "rope":
        q = rope(q, positions, theta)
        k = rope(k, positions, theta)
    scale = 1.0 / math.sqrt(hd)
    window = cfg.sliding_window if local else None

    if kind == "paged_decode":
        # Paged decode: no dense (B, S) view is ever assembled — the PUL
        # preload ring pulls physical pages in page-table order and the
        # current token's K/V (not yet in any page) merges in after the
        # stream. The kernel reads THIS layer out of the full per-layer
        # planes (SMEM layer scalar) and commits k_new/v_new in its fused
        # epilogue. Sliding windows are an in-kernel mask term (paged
        # layouts are token-indexed, never rings).
        assert T == 1, "paged decode processes one token per step"
        from repro.kernels.pul_attention import (
            pul_paged_sweep_decode_attention)
        idx = jnp.asarray(cache["idx"], jnp.int32).reshape(B)
        kp, vp = paged.plane("k"), paged.plane("v")
        k_new = k[:, 0].astype(kp.dtype)
        v_new = v[:, 0].astype(vp.dtype)
        out, kp, vp = pul_paged_sweep_decode_attention(
            q[:, 0], kp, vp, paged.layer, paged.page_table, idx,
            k_new, v_new, paged.frames, paged.offsets, scale=scale,
            softcap=cfg.attn_softcap, window=window, cfg=paged.pul_cfg)
        paged.set_plane("k", kp)
        paged.set_plane("v", vp)
        out = out[:, None]
        new_cache = {"k": k_new, "v": v_new, "idx": idx + 1}
    elif kind == "decode":
        # Per-slot fill levels: idx is a (B,) vector — each serving slot
        # tracks its own sequence length, which is what lets a continuous-
        # batching engine refill one slot without touching the others.
        # Global layers: cache holds max_seq slots, token t at row t.
        # Local layers, non-paged: cache is a RING of `window` slots (token t
        # lives at slot t % window); overwriting implements the sliding
        # window, so the mask only needs "slot already filled".
        # Local layers, paged_kv: cache is dense token-indexed like global
        # (pages must map 1:1 onto token ranges), so the sliding window is an
        # explicit mask term instead.
        assert T == 1, "decode processes one token per step"
        idx = jnp.broadcast_to(jnp.asarray(cache["idx"], jnp.int32), (B,))
        S = cache["k"].shape[1]
        write = jax.lax.rem(idx, S)                       # (B,)
        rows = jnp.arange(B)
        kc = cache["k"].at[rows, write].set(k[:, 0].astype(cache["k"].dtype))
        vc = cache["v"].at[rows, write].set(v[:, 0].astype(cache["v"].dtype))
        jk = jnp.arange(S)[None, :]
        mask2d = jk <= idx[:, None]                       # (B, S)
        if cfg.paged_kv and window is not None and S > window:
            mask2d &= jk > idx[:, None] - window
        mask = mask2d[:, None, None, None, :]             # (B,1,1,1,S)
        out = _attend(q, kc, vc, mask=mask, softcap=cfg.attn_softcap, scale=scale)
        new_cache = {"k": kc, "v": vc, "idx": idx + 1}
    else:
        if T > 2 * KV_BLOCK:
            out = _attend_chunked(q, k, v, softcap=cfg.attn_softcap,
                                  scale=scale, window=window)
        else:
            mask = _causal_mask(T, T, offset=0, window=window)
            out = _attend(q, k, v, mask=mask, softcap=cfg.attn_softcap,
                          scale=scale)
        new_cache = None
        if kind == "prefill":
            kc, vc = k, v
            target = max_seq or T
            if window is not None and not cfg.paged_kv:
                target = min(window, target)
            if T > target:
                # keep the last `target` tokens, ring-aligned (slot = t % W)
                o = T % target
                kc = jnp.roll(k[:, T - target:], o, axis=1)
                vc = jnp.roll(v[:, T - target:], o, axis=1)
            elif T < target:
                pad = ((0, 0), (0, target - T), (0, 0), (0, 0))
                kc, vc = jnp.pad(k, pad), jnp.pad(v, pad)
            new_cache = {"k": kc.astype(jnp.bfloat16), "v": vc.astype(jnp.bfloat16),
                         "idx": jnp.full((B,), T, jnp.int32)}
    y = jnp.einsum("bthk,hkd->btd", out, p["wo"])
    return y, new_cache


def attention_cache_spec(cfg: ModelConfig, batch: int, max_seq: int):
    """Abstract cache entry for one attention layer (dry-run input_specs)."""
    K, hd = cfg.num_kv_heads, cfg.head_dim
    arr = jax.ShapeDtypeStruct((batch, max_seq, K, hd), jnp.bfloat16)
    return {"k": arr, "v": arr,
            "idx": jax.ShapeDtypeStruct((batch,), jnp.int32)}


def attention_cache_logical():
    kv = ("cache_batch", "cache_seq", "act_kv_heads", "head_dim")
    return {"k": kv, "v": kv, "idx": ("cache_batch",)}


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------
def mlp_params(cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict[str, Any]:
    D = cfg.d_model
    F = d_ff if d_ff is not None else cfg.d_ff
    dt = jnp.bfloat16
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {
            "w_gate": Param((D, F), ("embed", "ff"), dt, "fan_in"),
            "w_up": Param((D, F), ("embed", "ff"), dt, "fan_in"),
            "w_down": Param((F, D), ("ff", "embed"), dt, "fan_in"),
        }
    if cfg.mlp_type == "gelu":
        return {
            "w_in": Param((D, F), ("embed", "ff"), dt, "fan_in"),
            "w_out": Param((F, D), ("ff", "embed"), dt, "fan_in"),
        }
    raise ValueError(f"mlp_type {cfg.mlp_type} handled elsewhere")


def mlp_apply(p, x, *, cfg: ModelConfig):
    if cfg.mlp_type in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else (
            lambda t: jax.nn.gelu(t, approximate=True))
        h = act(x @ p["w_gate"]) * (x @ p["w_up"])
        return h @ p["w_down"]
    if cfg.mlp_type == "gelu":
        return jax.nn.gelu(x @ p["w_in"], approximate=True) @ p["w_out"]
    raise ValueError(cfg.mlp_type)


# --------------------------------------------------------------------------
# embedding + chunked cross-entropy (streamed over vocab tiles — the softmax
# analogue of PUL: the (B,S,V) logits tensor never materializes)
# --------------------------------------------------------------------------
def embedding_params(cfg: ModelConfig) -> Dict[str, Any]:
    V = cfg.padded_vocab
    p = {"table": Param((V, cfg.d_model), ("vocab", "embed"),
                        jnp.bfloat16, "embed")}
    if not cfg.tie_embeddings:
        p["lm_head"] = Param((V, cfg.d_model), ("vocab", "embed"),
                             jnp.bfloat16, "fan_in", scale=0.02)
    return p


def embed_apply(p, tokens, *, cfg: ModelConfig):
    x = jnp.take(p["table"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def _head_table(p, cfg: ModelConfig):
    return p["table"] if cfg.tie_embeddings else p["lm_head"]


def logits_apply(p, x, *, cfg: ModelConfig):
    """Full logits — decode path only (T=1), (B,1,V)."""
    w = _head_table(p, cfg)
    logits = jnp.einsum("btd,vd->btv", x, w).astype(jnp.float32)
    logits = _constrain(logits, ("batch", None, "vocab"))
    if cfg.final_softcap is not None:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return logits[..., : cfg.vocab_size]


def chunked_xent(p, x, targets, mask, *, cfg: ModelConfig):
    """Streaming cross-entropy over vocab tiles.

    Never materializes (B,S,V): scans vocab chunks, maintaining an online
    logsumexp and gathering the target logit on the fly. Each chunk is
    rematerialized in the backward pass (jax.checkpoint).
    x: (B,S,D) final hiddens; targets: (B,S) int32; mask: (B,S) {0,1}.
    Returns mean nll over masked tokens.
    """
    w = _head_table(p, cfg)
    V = cfg.vocab_size                       # true vocab (pads masked below)
    Vp, D = w.shape                          # padded table rows
    C = min(cfg.vocab_chunk, Vp)
    n_chunks = Vp // C
    wp = _constrain(w.reshape(n_chunks, C, D), (None, "vocab", "embed"))

    B, S, _ = x.shape
    neg = jnp.float32(NEG_INF)

    @jax.checkpoint
    def chunk_step(carry, inp):
        m, lse, tgt_logit = carry
        wc, off = inp
        logits = jnp.einsum("bsd,cd->bsc", x, wc).astype(jnp.float32)
        logits = _constrain(logits, ("batch", None, "vocab"))
        if cfg.final_softcap is not None:
            logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
        # mask padded vocab rows
        valid = (off + jnp.arange(C)) < V
        logits = jnp.where(valid[None, None, :], logits, neg)
        cmax = jnp.max(logits, axis=-1)
        new_m = jnp.maximum(m, cmax)
        lse = jnp.exp(m - new_m) * lse + jnp.sum(
            jnp.exp(logits - new_m[..., None]), axis=-1)
        # gather target logit if it falls in this chunk
        loc = targets - off
        in_chunk = (loc >= 0) & (loc < C)
        gathered = jnp.take_along_axis(
            logits, jnp.clip(loc, 0, C - 1)[..., None], axis=-1)[..., 0]
        tgt_logit = jnp.where(in_chunk, gathered, tgt_logit)
        return (new_m, lse, tgt_logit), ()

    init = (jnp.full((B, S), neg, jnp.float32),
            jnp.zeros((B, S), jnp.float32),
            jnp.full((B, S), neg, jnp.float32))
    offs = jnp.arange(n_chunks, dtype=jnp.int32) * C
    (m, lse, tgt_logit), _ = jax.lax.scan(chunk_step, init, (wp, offs))
    nll = (m + jnp.log(lse)) - tgt_logit
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
