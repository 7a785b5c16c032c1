"""Plain float32 reference of a dense GQA decoder (Qwen2 / Qwen3 family).

Written from the published architecture, in `jax.numpy`, with no kernel,
cache or batching, and every matrix product at `Precision.HIGHEST`:

  x = embed[tokens]
  per layer:  h = rmsnorm(x) * w_attn_norm
              q, k, v = h Wq (+bq), h Wk (+bk), h Wv (+bv)       (GQA)
              q, k = rmsnorm_head(q) * w_q_norm, ... (Qwen3 qk-norm)
              q, k = rope(q), rope(k)    half-split, theta from the config
              x += softmax(q k^T / sqrt(hd), causal) v Wo
              h = rmsnorm(x) * w_mlp_norm
              x += (silu(h Wg) * h Wu) Wd
  logits = rmsnorm(x) * w_final_norm @ head^T        (head = embed if tied)

It reads the configuration file's keys and the weight dict of
`chipbench/weights.py`, and imports nothing of the program under test. One
sequence runs at a time, layer after layer inside one scan, so the largest
temporaries are one layer's float32 weights and one head's (T, T) scores.

`quant` switches the control on: every matmul weight goes through float8
(e4m3, one scale per output channel) before the float32 product.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0            # largest finite float8_e4m3fn


def fp8(w, axes):
    """`w` rounded through float8 e4m3 with one scale per slice over the
    contraction `axes`; returned in float32."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _w(w, axes, quant):
    return fp8(w, axes) if quant else w.astype(jnp.float32)


def _rmsnorm(x, weight, eps):
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * weight.astype(jnp.float32)


def _rope(x, theta):
    """x: (T, n, hd); rotate the two halves of each head."""
    T, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _layer(arch, quant, x, lw):
    eps, theta = arch["rms_norm_eps"], float(arch["rope_theta"])
    H, K = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd = arch["head_dim"]
    T = x.shape[0]
    h = _rmsnorm(x, lw["attn_norm"], eps)
    q = jnp.einsum("td,dnh->tnh", h, _w(lw["wq"], 0, quant), precision=HI)
    k = jnp.einsum("td,dnh->tnh", h, _w(lw["wk"], 0, quant), precision=HI)
    v = jnp.einsum("td,dnh->tnh", h, _w(lw["wv"], 0, quant), precision=HI)
    if arch["qkv_bias"]:
        q = q + lw["bq"].astype(jnp.float32)
        k = k + lw["bk"].astype(jnp.float32)
        v = v + lw["bv"].astype(jnp.float32)
    if arch["qk_norm"]:
        q = _rmsnorm(q, lw["q_norm"], eps)
        k = _rmsnorm(k, lw["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, H // K, axis=1)          # head n reads kv head n // G
    v = jnp.repeat(v, H // K, axis=1)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    def head(qh, kh, vh):
        s = jnp.dot(qh, kh.T, precision=HI) / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.dot(p, vh, precision=HI)

    att = jax.lax.map(lambda a: head(*a), (q.transpose(1, 0, 2),
                                           k.transpose(1, 0, 2),
                                           v.transpose(1, 0, 2)))
    x = x + jnp.einsum("nth,nhd->td", att, _w(lw["wo"], (0, 1), quant),
                       precision=HI)
    h = _rmsnorm(x, lw["mlp_norm"], eps)
    g = jnp.dot(h, _w(lw["w_gate"], 0, quant), precision=HI)
    u = jnp.dot(h, _w(lw["w_up"], 0, quant), precision=HI)
    return x + jnp.dot(jax.nn.silu(g) * u, _w(lw["w_down"], 0, quant),
                       precision=HI)


LAYER_KEYS = ("attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "w_gate",
              "w_up", "w_down", "bq", "bk", "bv", "q_norm", "k_norm")


@functools.partial(jax.jit, static_argnames=("arch_items", "quant"))
def _hidden(w, tokens, *, arch_items, quant):
    arch = dict(arch_items)
    x = _w(w["embed"][tokens], 1, quant)       # row-wise scales: gather first
    layers = {k: w[k] for k in LAYER_KEYS if k in w}
    x, _ = jax.lax.scan(lambda x, lw: (_layer(arch, quant, x, lw), None),
                        x, layers)
    return _rmsnorm(x, w["final_norm"], arch["rms_norm_eps"])


VOCAB_BLOCK = 16384


@functools.partial(jax.jit, static_argnames=("arch_items", "quant", "rows"))
def _head_stats(w, hidden, targets, *, arch_items, quant, rows):
    arch = dict(arch_items)
    V = arch["vocab_size"]
    head = w["embed"] if arch["tie_word_embeddings"] else w["lm_head"]
    R, D = head.shape                       # R >= V: zero rows past V
    C = VOCAB_BLOCK if R % VOCAB_BLOCK == 0 else R
    T = hidden.shape[0]

    def block(a):                           # one block of positions
        hb, tb = a

        def chunk(carry, c):                # one block of the vocabulary
            top, at, best = carry
            wc = jax.lax.dynamic_slice_in_dim(head, c * C, C)
            logits = jnp.dot(hb, _w(wc, 1, quant).T, precision=HI)
            ids = c * C + jnp.arange(C)
            logits = jnp.where(ids[None, :] < V, logits, -jnp.inf)
            ctop = jnp.max(logits, axis=-1)
            cbest = c * C + jnp.argmax(logits, axis=-1).astype(jnp.int32)
            loc = tb - c * C
            cat = jnp.take_along_axis(
                logits, jnp.clip(loc, 0, C - 1)[:, None], axis=-1)[:, 0]
            at = jnp.where((loc >= 0) & (loc < C), cat, at)
            best = jnp.where(ctop > top, cbest, best)
            return (jnp.maximum(top, ctop), at, best), None

        init = (jnp.full((rows,), -jnp.inf), jnp.full((rows,), -jnp.inf),
                jnp.zeros((rows,), jnp.int32))
        out, _ = jax.lax.scan(chunk, init, jnp.arange(R // C))
        return out

    top, at, best = jax.lax.map(block, (hidden.reshape(T // rows, rows, D),
                                        targets.reshape(T // rows, rows)))
    return top.reshape(T), at.reshape(T), best.reshape(T)


def _items(arch: dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "vocab_size", "qkv_bias", "qk_norm",
            "tie_word_embeddings")
    return tuple((k, arch[k]) for k in keys)


def hidden(arch: dict, w: dict, tokens, *, quant: bool = False):
    """Final normed hidden states (T, D) float32 of one token sequence."""
    return _hidden(w, jnp.asarray(tokens, jnp.int32), arch_items=_items(arch),
                   quant=quant)


def head_stats(arch: dict, w: dict, h, targets, *, quant: bool = False):
    """Per position: (largest logit, logit of `targets`, argmax token).
    T must be a multiple of 256 (the vocab product runs in row blocks)."""
    rows = min(256, h.shape[0])
    return _head_stats(w, h, jnp.asarray(targets, jnp.int32),
                       arch_items=_items(arch), quant=quant, rows=rows)
