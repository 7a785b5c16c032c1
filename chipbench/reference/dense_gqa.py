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

It reads the configuration file's keys and the weights that
`chipbench/weights.py` makes to `shapes` below, the family's layout, and
imports nothing of the program under test. One sequence runs at a time,
layer after layer inside one scan, so the largest temporaries are one
layer's float32 weights and one head's (T, T) scores.

`quant` switches the control on: every matmul weight goes through float8
(e4m3, one scale per output channel) before the float32 product.

The same equations give the work the metrics divide by: `prompt_flops`,
`decode_flops` and `sweep_work` count each multiply-add as 2 and only the
work the answer needs: the projections, the MLP and causal attention over
the positions already written, plus the output head once for each token
whose logits are used (a prompt's last position and every generated
token). Recomputation, padding, masked blocks and empty slots do not count.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0            # largest finite float8_e4m3fn
BIAS_STD = 0.1             # q/k/v biases (Qwen2): large enough to move the
                           # logits
EMBED_STD = 0.02           # embedding and output head rows
BF16 = 2


def shapes(arch: dict, vocab_rows: int) -> dict:
    """The weight layout: {name: (shape, dtype, std, or None for a norm
    weight)}. Per-layer tensors are stacked on a leading layer axis; norm
    weights are plain RMSNorm scales."""
    L, D = arch["num_hidden_layers"], arch["hidden_size"]
    H, K = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd, F = arch["head_dim"], arch["intermediate_size"]
    bf, f32 = jnp.bfloat16, jnp.float32
    out = {
        "embed": ((vocab_rows, D), bf, EMBED_STD),
        "final_norm": ((D,), f32, None),
        "attn_norm": ((L, D), f32, None),
        "mlp_norm": ((L, D), f32, None),
        "wq": ((L, D, H, hd), bf, 1 / math.sqrt(D)),
        "wk": ((L, D, K, hd), bf, 1 / math.sqrt(D)),
        "wv": ((L, D, K, hd), bf, 1 / math.sqrt(D)),
        "wo": ((L, H, hd, D), bf, 1 / math.sqrt(H * hd)),
        "w_gate": ((L, D, F), bf, 1 / math.sqrt(D)),
        "w_up": ((L, D, F), bf, 1 / math.sqrt(D)),
        "w_down": ((L, F, D), bf, 1 / math.sqrt(F)),
    }
    if not arch["tie_word_embeddings"]:
        out["lm_head"] = ((vocab_rows, D), bf, EMBED_STD)
    if arch["qkv_bias"]:
        out["bq"] = ((L, H, hd), bf, BIAS_STD)
        out["bk"] = ((L, K, hd), bf, BIAS_STD)
        out["bv"] = ((L, K, hd), bf, BIAS_STD)
    if arch["qk_norm"]:
        out["q_norm"] = ((L, hd), f32, None)
        out["k_norm"] = ((L, hd), f32, None)
    return out


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


# --------------------------------------------------------------------------
# work: model FLOPs and HBM bytes
# --------------------------------------------------------------------------
def body_params(arch: dict) -> int:
    """Matmul weights per token of all layers (embedding gather excluded)."""
    D, H = arch["hidden_size"], arch["num_attention_heads"]
    K, hd = arch["num_key_value_heads"], arch["head_dim"]
    F = arch["intermediate_size"]
    per_layer = D * (H + 2 * K) * hd + H * hd * D + 3 * D * F
    return arch["num_hidden_layers"] * per_layer


def head_params(arch: dict) -> int:
    return arch["vocab_size"] * arch["hidden_size"]


def attn_flops(arch: dict, keys: int) -> float:
    """QK^T and PV of one query over `keys` positions, all layers."""
    return (4.0 * arch["num_attention_heads"] * arch["head_dim"]
            * arch["num_hidden_layers"] * keys)


def prompt_flops(arch: dict, n: int) -> float:
    """A prefill of an n-token prompt: every position through the body,
    causal attention, and the head at the last position."""
    causal_keys = n * (n + 1) / 2
    return (2.0 * body_params(arch) * n + 2.0 * head_params(arch)
            + attn_flops(arch, causal_keys))


def decode_flops(arch: dict, cached: int) -> float:
    """One generated token attending to `cached` earlier positions and
    itself."""
    return (2.0 * (body_params(arch) + head_params(arch))
            + attn_flops(arch, cached + 1))


def sweep_work(arch: dict, cached: int) -> tuple:
    """(FLOPs, HBM bytes) the decode attention needs for one slot with
    `cached` positions in its pages: read each cached K and V row once."""
    L, K = arch["num_hidden_layers"], arch["num_key_value_heads"]
    hd = arch["head_dim"]
    return (attn_flops(arch, cached), float(cached * L * 2 * K * hd * BF16))
