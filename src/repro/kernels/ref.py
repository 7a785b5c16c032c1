"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def sum_ref(data: jax.Array, trace: jax.Array) -> jax.Array:
    """Trace-driven aggregation (paper's SUM microbench): sum of the rows of
    `data` selected by `trace` (with repetition)."""
    return jnp.sum(data[trace].astype(jnp.float32))


def gather_ref(data: jax.Array, trace: jax.Array) -> jax.Array:
    """Random row gather: out[i] = data[trace[i]]."""
    return data[trace]


def matmul_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32))


def attention_ref(q, k, v, *, causal: bool = True, scale: Optional[float] = None,
                  softcap: Optional[float] = None,
                  window: Optional[int] = None) -> jax.Array:
    """q: (B,H,T,hd); k,v: (B,K,S,hd) with H % K == 0 (GQA)."""
    B, H, T, hd = q.shape
    K = k.shape[1]
    G = H // K
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    kk = jnp.repeat(k, G, axis=1)
    vv = jnp.repeat(v, G, axis=1)
    logits = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    S = k.shape[2]
    if causal:
        i = jnp.arange(T)[:, None] + (S - T)   # queries end-aligned with keys
        j = jnp.arange(S)[None, :]
        m = j <= i
        if window is not None:
            m &= j > i - window
        logits = jnp.where(m[None, None], logits, -2.0e38)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhts,bhsd->bhtd", p, vv.astype(jnp.float32)).astype(q.dtype)


def paged_decode_ref(q, k_pages, v_pages, page_table, lengths, k_new, v_new,
                     *, scale: Optional[float] = None,
                     softcap: Optional[float] = None,
                     window: Optional[int] = None) -> jax.Array:
    """One layer of the paged sweep decode. q: (B,H,hd); k_pages/v_pages:
    (NF,K,P,W >= hd) the layer's page frames; page_table: (B,n) the frame
    of each slot's logical page; lengths: (B,) cached rows; k_new/v_new:
    (B,K,hd) the current token, at position lengths[b] and always visible
    (the window is anchored there)."""
    B, H, hd = q.shape
    K, P = k_pages.shape[1:3]
    S = page_table.shape[1] * P

    def stream(pages, new):             # (B, H, S + 1, hd): cache, then new
        d = pages[page_table][..., :hd].transpose(0, 2, 1, 3, 4)
        d = jnp.concatenate([d.reshape(B, K, S, hd), new[:, :, None]], 2)
        return jnp.repeat(d.astype(jnp.float32), H // K, axis=1)

    kk, vv = stream(k_pages, k_new), stream(v_pages, v_new)
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    logits = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32), kk) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    jk = jnp.arange(S + 1)[None, None, :]
    n = jnp.asarray(lengths)[:, None, None]
    keep = jk < n
    if window is not None:
        keep &= jk > n - window
    keep |= jk == S
    p = jax.nn.softmax(jnp.where(keep, logits, -2.0e38), axis=-1)
    return jnp.einsum("bhs,bhsd->bhd", p, vv)


def paged_mla_decode_ref(q_abs, q_rope, ckv_pages, kr_pages, page_table,
                         lengths, c_new, r_new, *, scale: float) -> jax.Array:
    """One layer of the absorbed-MLA paged sweep decode. q_abs: (B,H,kvr),
    q_rope: (B,H,dr); ckv_pages/kr_pages: (NF,P,C >= kvr)/(NF,P,R >= dr)
    the layer's compressed page frames; c_new/r_new: (B,kvr)/(B,dr) the
    current token, always visible. The compressed cache doubles as the
    value stream: returns (B,H,kvr)."""
    B, H, kvr = q_abs.shape
    dr = q_rope.shape[-1]
    S = page_table.shape[1] * ckv_pages.shape[1]

    def stream(pages, new, w):          # (B, S + 1, w): cache, then new
        d = pages[page_table][..., :w].reshape(B, S, w)
        return jnp.concatenate([d, new[:, None]], 1).astype(jnp.float32)

    cd, rd = stream(ckv_pages, c_new, kvr), stream(kr_pages, r_new, dr)
    logits = (jnp.einsum("bhr,bsr->bhs", q_abs.astype(jnp.float32), cd)
              + jnp.einsum("bhd,bsd->bhs", q_rope.astype(jnp.float32), rd)
              ) * scale
    jk = jnp.arange(S + 1)[None, None, :]
    keep = (jk < jnp.asarray(lengths)[:, None, None]) | (jk == S)
    p = jax.nn.softmax(jnp.where(keep, logits, -2.0e38), axis=-1)
    return jnp.einsum("bhs,bsr->bhr", p, cd)


def filter_ref(data: jax.Array, threshold) -> jax.Array:
    """Selection bit-vector (paper Exp. 5): bit i set iff data[i,0] > thr.
    Packed little-endian into int32 words."""
    bits = (data[:, 0] > threshold).astype(jnp.uint32)
    n = bits.shape[0]
    pad = (-n) % 32
    bits = jnp.pad(bits, (0, pad))
    words = bits.reshape(-1, 32) << jnp.arange(32, dtype=jnp.uint32)[None, :]
    return jnp.bitwise_or.reduce(words, axis=1).astype(jnp.uint32)


def filter_materialize_ref(data: jax.Array, threshold) -> jax.Array:
    """Full materialization baseline: selected rows kept, others zeroed
    (fixed-shape variant of result-set materialization)."""
    keep = data[:, 0] > threshold
    return jnp.where(keep[:, None], data, 0)


def decode_attention_ref(q, k, v, length, *, scale=None, softcap=None):
    """q: (B,H,hd); k,v: (B,K,S,hd); length: (B,) valid entries."""
    import numpy as _np
    B, H, hd = q.shape
    K, S = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    kk = jnp.repeat(k, G, axis=1)
    vv = jnp.repeat(v, G, axis=1)
    logits = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    mask = jnp.arange(S)[None, None, :] < jnp.asarray(length)[:, None, None]
    logits = jnp.where(mask, logits, -2.0e38)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhs,bhsd->bhd", p, vv.astype(jnp.float32)).astype(q.dtype)
