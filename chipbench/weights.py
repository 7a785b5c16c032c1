"""Seeded random weights of a dense GQA decoder, made on the device.

The benchmark makes the weights itself, from `--seed`, so that the plain
reference and the program under test read the same numbers and neither takes
anything the other made. The layout is the reference's own (plain RMSNorm
weights, per-layer tensors stacked on a leading layer axis); `adapter.py`
hands the same buffers to the program in its tree.

One jitted call makes every tensor in the type it is served in: matmul
weights in bfloat16, norm weights in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

BIAS_STD = 0.1      # q/k/v biases (Qwen2): large enough to move the logits
NORM_STD = 0.1      # norm weights are 1 + NORM_STD * N(0, 1)
EMBED_STD = 0.02    # embedding and output head rows


def key_data(seed: int) -> np.ndarray:
    """Raw threefry key words for any non-negative integer seed (more than
    32 bits included)."""
    return np.random.SeedSequence(seed).generate_state(2, dtype=np.uint32)


def shapes(arch: dict, vocab_rows: int) -> dict:
    """{name: (shape, dtype, std or None for a norm weight)}."""
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


def make(arch: dict, seed: int, vocab_rows: int) -> dict:
    """Every weight from `seed`, on the default device, in one jitted call.
    Rows of the embedding and head past `vocab_size` are zero padding."""
    spec = shapes(arch, vocab_rows)
    V = arch["vocab_size"]

    def build(words):
        key = jax.random.wrap_key_data(words)
        out = {}
        for i, (name, (shape, dtype, std)) in enumerate(sorted(spec.items())):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shape, jnp.float32)
            if std is None:
                out[name] = (1.0 + NORM_STD * z).astype(dtype)
                continue
            w = (z * std).astype(dtype)
            if name in ("embed", "lm_head") and shape[0] > V:
                w = jnp.where(jnp.arange(shape[0])[:, None] < V, w, 0)
            out[name] = w
        return out

    return jax.jit(build)(jnp.asarray(key_data(seed)))
