"""Seeded random weights of any architecture family, made on the device.

The benchmark makes the weights itself, from `--seed`, so that the plain
reference and the program under test read the same numbers and neither takes
anything the other made. The layout is the reference's own: `shapes(arch,
vocab_rows)` of the family's `reference/<family>.py` names every tensor with
its shape, dtype and standard deviation (None for a norm weight), and
names its vocabulary tables `embed` and, when untied, `lm_head`;
`adapter.py` hands the same buffers to the program in its tree.

One jitted call makes every tensor in the type it is served in. Tensor i,
in the order of their names, is drawn from the seed's key folded with i.
"""
from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import discover

NORM_STD = 0.1      # norm weights are 1 + NORM_STD * N(0, 1)


def key_data(seed: int) -> np.ndarray:
    """Raw threefry key words for any non-negative integer seed (more than
    32 bits included)."""
    return np.random.SeedSequence(seed).generate_state(2, dtype=np.uint32)


def make(arch: dict, seed: int, vocab_rows: int, *,
         base: Path = discover.BENCH) -> dict:
    """Every weight from `seed`, on the default device, in one jitted call.
    Rows of the embedding and head past `vocab_size` are zero padding."""
    spec = discover.family(arch, base).reference.shapes(arch, vocab_rows)
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
