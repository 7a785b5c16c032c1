"""build_model(config): the zoo's single entry point.

Wraps transformer.py into a Model record with bound apply fns, abstract
parameter/cache trees, and per-shape input_specs (ShapeDtypeStructs for the
dry-run; the modality frontends are stubs supplying precomputed embeddings
per the assignment).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.models import transformer as T
from repro.models import module as M


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    params: Any                   # Param tree (abstract)
    loss: Callable                # (params, batch) -> scalar
    prefill: Callable             # (params, batch) -> (logits, caches)
    decode_step: Callable         # (params, batch, caches) -> (logits, caches)
    paged_decode_step: Callable   # (params, batch, caches, page planes)
                                  # -> (logits, caches, planes): the sweep

    def init(self, key):
        return M.init_tree(key, self.params)

    def abstract_params(self):
        return M.abstract_tree(self.params)

    def num_params(self) -> int:
        return M.count_params(self.params)

    def cache_specs(self, batch: int, max_seq: int):
        return T.cache_specs(self.cfg, batch, max_seq)


def build_model(cfg: ModelConfig) -> Model:
    return Model(
        cfg=cfg,
        params=T.model_params(cfg),
        loss=functools.partial(T.loss_fn, cfg=cfg),
        prefill=functools.partial(T.prefill_fn, cfg=cfg),
        decode_step=functools.partial(T.decode_fn, cfg=cfg),
        paged_decode_step=functools.partial(T.paged_decode_fn, cfg=cfg),
    )


# --------------------------------------------------------------------------
# input specs (dry-run stand-ins; also documents the data contract)
# --------------------------------------------------------------------------
def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ModelConfig, shape: InputShape) -> Dict[str, Any]:
    """Returns {"batch": tree, "batch_logical": tree[, "caches",
    "caches_logical"]} for the given (arch x shape) cell.

    The modality frontend STUB manifests here: internvl2 receives 256
    precomputed ViT patch embeddings, musicgen 64 conditioning frames; token
    count shrinks so total sequence stays shape.seq_len.
    """
    B, S = shape.global_batch, shape.seq_len
    Tf = cfg.frontend_tokens if cfg.frontend else 0
    St = S - Tf
    out: Dict[str, Any] = {}
    if shape.kind == "train":
        batch = {
            "tokens": _sds((B, St), jnp.int32),
            "targets": _sds((B, St), jnp.int32),
            "loss_mask": _sds((B, St), jnp.float32),
        }
        logical = {
            "tokens": ("batch", "seq"),
            "targets": ("batch", "seq"),
            "loss_mask": ("batch", "seq"),
        }
    elif shape.kind == "prefill":
        batch = {"tokens": _sds((B, St), jnp.int32)}
        logical = {"tokens": ("batch", "seq")}
    elif shape.kind == "decode":
        batch = {
            "tokens": _sds((B, 1), jnp.int32),
            "pos0": _sds((B,), jnp.int32),
        }
        logical = {"tokens": ("batch", None), "pos0": ("batch",)}
        caches, caches_logical = T.cache_specs(cfg, B, S)
        out["caches"] = caches
        out["caches_logical"] = caches_logical
    else:
        raise ValueError(shape.kind)
    if Tf and shape.kind != "decode":
        batch["frontend_embeds"] = _sds((B, Tf, cfg.d_model), jnp.bfloat16)
        logical["frontend_embeds"] = ("batch", "seq", None)
    out["batch"] = batch
    out["batch_logical"] = logical
    return out


def demo_batch(cfg: ModelConfig, batch_size: int, seq_len: int, key=None):
    """Concrete small batch for smoke tests / examples (train kind)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    Tf = cfg.frontend_tokens if cfg.frontend else 0
    tokens = jax.random.randint(k1, (batch_size, seq_len), 0, cfg.vocab_size,
                                jnp.int32)
    batch = {
        "tokens": tokens,
        "targets": jnp.roll(tokens, -1, axis=1),
        "loss_mask": jnp.ones((batch_size, seq_len), jnp.float32),
    }
    if Tf:
        batch["frontend_embeds"] = (
            jax.random.normal(k2, (batch_size, Tf, cfg.d_model), jnp.float32)
            .astype(jnp.bfloat16) * 0.02)
    return batch
