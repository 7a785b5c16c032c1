"""Backbone assembly: pattern-scanned heterogeneous layer stacks.

A config's ``pattern`` (e.g. gemma3's 5x local + 1 global, zamba2's
(mamba, mamba, shared_attn)) forms one *group*; ``num_groups`` groups are
``lax.scan``-ed with stacked parameters, keeping HLO size O(1) in depth and
enabling clean FSDP all-gather scheduling. Shared blocks (zamba2) live
outside the scan and are closed over; their per-invocation LoRA deltas are
scanned. DeepSeek's dense layer 0 is unscanned.

Three entry points per model: ``loss`` (train), ``prefill`` (process prompt,
emit caches), ``decode_step`` (one token, incremental state).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.module import Param, stack_params
from repro.runtime.sharding import constrain


# --------------------------------------------------------------------------
# parameter trees
# --------------------------------------------------------------------------
def _attn_mlp_block_params(cfg: ModelConfig, kind: str) -> Dict[str, Any]:
    p: Dict[str, Any] = {"norm1": L.rms_norm_params(cfg.d_model),
                         "norm2": L.rms_norm_params(cfg.d_model)}
    if cfg.sandwich_norm:
        p["post_norm1"] = L.rms_norm_params(cfg.d_model)
        p["post_norm2"] = L.rms_norm_params(cfg.d_model)
    p["attn"] = (MLA.mla_params(cfg) if cfg.attn_type == "mla"
                 else L.attention_params(cfg))
    if kind == "moe":
        p["mlp"] = MOE.moe_params(cfg)
    else:
        p["mlp"] = L.mlp_params(cfg)
    return p


def _lora_params(cfg: ModelConfig) -> Dict[str, Any]:
    D, H, hd, r = cfg.d_model, cfg.num_heads, cfg.head_dim, cfg.shared_lora_rank
    K = cfg.num_kv_heads
    dt = jnp.bfloat16
    return {
        "q_a": Param((D, r), ("embed", "lora"), dt, "fan_in"),
        "q_b": Param((r, H * hd), ("lora", "dinner"), dt, "zeros"),
        "k_a": Param((D, r), ("embed", "lora"), dt, "fan_in"),
        "k_b": Param((r, K * hd), ("lora", "dinner"), dt, "zeros"),
        "v_a": Param((D, r), ("embed", "lora"), dt, "fan_in"),
        "v_b": Param((r, K * hd), ("lora", "dinner"), dt, "zeros"),
    }


def block_params(cfg: ModelConfig, kind: str) -> Dict[str, Any]:
    if kind in ("global", "local", "moe", "dense"):
        return _attn_mlp_block_params(cfg, kind)
    if kind == "rwkv":
        return SSM.rwkv_params(cfg)
    if kind == "mamba":
        return SSM.mamba_params(cfg)
    if kind == "shared_attn":
        return _lora_params(cfg) if cfg.shared_lora_rank else {}
    raise ValueError(f"unknown block kind {kind}")


def model_params(cfg: ModelConfig) -> Dict[str, Any]:
    group = {f"{i}:{kind}": block_params(cfg, kind)
             for i, kind in enumerate(cfg.pattern)}
    p: Dict[str, Any] = {
        "embedding": L.embedding_params(cfg),
        "final_norm": L.rms_norm_params(cfg.d_model),
        "groups": stack_params(group, cfg.num_groups, "layers"),
    }
    if cfg.first_dense_layers:
        p["dense"] = {str(i): _attn_mlp_block_params(cfg, "dense")
                      for i in range(cfg.first_dense_layers)}
    if "shared_attn" in cfg.pattern:
        p["shared"] = _attn_mlp_block_params(cfg, "global")
    return p


# --------------------------------------------------------------------------
# caches
# --------------------------------------------------------------------------
def _block_cache_spec(cfg: ModelConfig, kind: str, batch: int, max_seq: int):
    if kind in ("global", "moe", "dense"):
        if cfg.attn_type == "mla":
            return MLA.mla_cache_spec(cfg, batch, max_seq)
        return L.attention_cache_spec(cfg, batch, max_seq)
    if kind == "local":
        if cfg.paged_kv:            # dense token-indexed layout (pageable)
            return L.attention_cache_spec(cfg, batch, max_seq)
        window = min(cfg.sliding_window or max_seq, max_seq)
        return L.attention_cache_spec(cfg, batch, window)
    if kind == "shared_attn":
        return L.attention_cache_spec(cfg, batch, max_seq)
    if kind == "rwkv":
        return SSM.rwkv_state_spec(cfg, batch)
    if kind == "mamba":
        return SSM.mamba_state_spec(cfg, batch)
    raise ValueError(kind)


def _block_cache_logical(cfg: ModelConfig, kind: str):
    if kind in ("global", "moe", "dense", "local", "shared_attn"):
        if cfg.attn_type == "mla":
            return MLA.mla_cache_logical()
        return L.attention_cache_logical()
    if kind == "rwkv":
        return SSM.rwkv_state_logical()
    if kind == "mamba":
        return SSM.mamba_state_logical()
    raise ValueError(kind)


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int):
    """Abstract decode-cache tree + parallel logical-axes tree."""
    group_spec = {f"{i}:{kind}": _block_cache_spec(cfg, kind, batch, max_seq)
                  for i, kind in enumerate(cfg.pattern)}
    group_logical = {f"{i}:{kind}": _block_cache_logical(cfg, kind)
                     for i, kind in enumerate(cfg.pattern)}
    # stack over scanned groups
    spec = {"groups": jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((cfg.num_groups, *s.shape), s.dtype),
        group_spec)}
    logical = {"groups": jax.tree.map(
        lambda l: (None, *l), group_logical,
        is_leaf=lambda x: isinstance(x, tuple))}
    if cfg.first_dense_layers:
        spec["dense"] = {str(i): _block_cache_spec(cfg, "dense", batch, max_seq)
                         for i in range(cfg.first_dense_layers)}
        logical["dense"] = {str(i): _block_cache_logical(cfg, "dense")
                            for i in range(cfg.first_dense_layers)}
    return spec, logical


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------
def _merged_lora_attn(shared_attn, lora, cfg: ModelConfig):
    """Zamba2: shared attention weights + per-invocation LoRA deltas."""
    if not lora:
        return shared_attn
    H, K, hd, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    p = dict(shared_attn)
    p["wq"] = shared_attn["wq"] + (lora["q_a"] @ lora["q_b"]).reshape(D, H, hd)
    p["wk"] = shared_attn["wk"] + (lora["k_a"] @ lora["k_b"]).reshape(D, K, hd)
    p["wv"] = shared_attn["wv"] + (lora["v_a"] @ lora["v_b"]).reshape(D, K, hd)
    return p


def block_apply(p, shared, x, *, cfg: ModelConfig, kind: str, positions,
                step_kind: str, cache=None, max_seq=None, paged=None):
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.float32(0.0)
    # recurrent blocks have no pageable KV: a paged decode step is an
    # ordinary decode step for them (state rides in the resident tree)
    ssm_kind = "decode" if step_kind == "paged_decode" else step_kind
    if kind == "rwkv":
        x, new_cache = SSM.rwkv_block_apply(p, x, cfg=cfg, kind=ssm_kind,
                                            state=cache)
        return x, new_cache, aux
    if kind == "mamba":
        x, new_cache = SSM.mamba_block_apply(p, x, cfg=cfg, kind=ssm_kind,
                                             state=cache)
        return x, new_cache, aux

    if kind == "shared_attn":
        blk = shared
        attn_p = _merged_lora_attn(shared["attn"], p, cfg)
    else:
        blk = p
        attn_p = p["attn"]

    h = L.rms_norm(x, blk["norm1"], cfg.norm_eps)
    if cfg.attn_type == "mla":
        h, new_cache = MLA.mla_apply(attn_p, h, cfg=cfg, positions=positions,
                                     kind=step_kind, cache=cache,
                                     max_seq=max_seq, paged=paged)
    else:
        h, new_cache = L.attention_apply(
            attn_p, h, cfg=cfg, positions=positions, kind=step_kind,
            local=(kind == "local"), cache=cache, max_seq=max_seq,
            paged=paged)
    if cfg.sandwich_norm:
        h = L.rms_norm(h, blk["post_norm1"], cfg.norm_eps)
    x = x + h

    h = L.rms_norm(x, blk["norm2"], cfg.norm_eps)
    if kind == "moe":
        h, aux = MOE.moe_apply(blk["mlp"], h, cfg=cfg)
    else:
        h = L.mlp_apply(blk["mlp"], h, cfg=cfg)
    if cfg.sandwich_norm:
        h = L.rms_norm(h, blk["post_norm2"], cfg.norm_eps)
    x = x + h
    return x, new_cache, aux


# --------------------------------------------------------------------------
# backbone
# --------------------------------------------------------------------------
def _embed_inputs(params, batch, cfg: ModelConfig):
    x = L.embed_apply(params["embedding"], batch["tokens"], cfg=cfg)
    if cfg.frontend is not None and "frontend_embeds" in batch:
        fe = batch["frontend_embeds"].astype(x.dtype)
        x = jnp.concatenate([fe, x], axis=1)
    B, S, _ = x.shape
    pos0 = batch.get("pos0", None)
    base = jnp.arange(S, dtype=jnp.int32)[None, :]
    positions = base if pos0 is None else base + pos0[:, None]
    if cfg.pos_embedding == "sinusoidal":
        x = x + L.sinusoidal_embedding(positions, cfg.d_model).astype(x.dtype)
    return x, positions


def backbone(params, x, positions, *, cfg: ModelConfig, step_kind: str,
             caches=None, max_seq=None, paged=None):
    """Runs dense prefix + scanned groups. Returns (x, new_caches, aux)."""
    aux_total = jnp.float32(0.0)
    # paged decode: `paged` is a PagedSweep carrying the full per-layer
    # page planes; the backbone sets its (prefix, layer) routing per block
    # and threads the grouped planes through the scan carry
    new_dense = {}
    if cfg.first_dense_layers:
        for i in range(cfg.first_dense_layers):
            c = None if caches is None else caches["dense"][str(i)]
            if paged is not None:
                paged.prefix = ("dense", str(i))
                paged.layer = 0         # dense planes have layer extent 1
            x, nc, aux = block_apply(params["dense"][str(i)], None, x, cfg=cfg,
                                     kind="dense", positions=positions,
                                     step_kind=step_kind, cache=c,
                                     max_seq=max_seq, paged=paged)
            new_dense[str(i)] = nc
            aux_total += aux

    shared = params.get("shared")

    def group_body(carry, inp):
        x, aux_acc = carry
        gp, gc = inp
        if step_kind == "train":
            # Name the group-boundary activation so the remat policy saves
            # EXACTLY this bf16 tensor per group (and nothing else). With
            # cfg.seq_shard_carry the scan CARRY (which partial_eval saves
            # per group) is sequence-sharded over the model axis — 16x
            # smaller residual stack; the body re-gathers it for compute
            # (§Perf B memory-term move for the giant MoE trainers).
            from jax.ad_checkpoint import checkpoint_name
            x = checkpoint_name(x, "group_carry")
            if cfg.seq_shard_carry:
                x = constrain(x, ("batch", None, None))   # gather to compute
        new_gc = {}
        for i, kind in enumerate(cfg.pattern):
            key = f"{i}:{kind}"
            c = None if gc is None else gc[key]
            if paged is not None:
                paged.prefix = ("groups", key)
            x, nc, aux = block_apply(gp[key], shared, x, cfg=cfg, kind=kind,
                                     positions=positions,
                                     step_kind=step_kind, cache=c,
                                     max_seq=max_seq, paged=paged)
            new_gc[key] = nc
            aux_acc = aux_acc + aux
        if step_kind == "train" and cfg.seq_shard_carry:
            x = constrain(x, ("batch", "seq_model", None))  # sharded carry
        else:
            x = constrain(x, ("batch", None, None))
        return (x, aux_acc), new_gc

    body = group_body
    if cfg.remat and step_kind == "train":
        body = jax.checkpoint(
            group_body, prevent_cse=False,
            policy=jax.checkpoint_policies.save_only_these_names("group_carry"))

    if step_kind == "train":
        (x, aux_total), _ = jax.lax.scan(
            lambda c, gp: (body(c, (gp, None))[0], None),
            (x, aux_total), params["groups"])
        new_caches = None
    elif step_kind == "prefill":
        (x, aux_total), new_gcaches = jax.lax.scan(
            lambda c, gp: body(c, (gp, None)),
            (x, aux_total), params["groups"])
        new_caches = {"groups": new_gcaches}
        if cfg.first_dense_layers:
            new_caches["dense"] = new_dense
    else:  # decode
        gkeys = (sorted(k for k in paged.planes if k.startswith("groups/"))
                 if paged is not None else [])
        if gkeys:
            # thread the grouped planes through the scan carry: iteration g
            # receives the planes as written by layers < g, the sweep kernel
            # updates row g in place (aliased outputs), and the final carry
            # is the fully committed store
            def sweep_body(carry, inp):
                inner, gplanes = carry
                gp, gc, g = inp
                paged.layer = g
                for pk in gkeys:
                    paged.planes[pk] = gplanes[pk]
                inner, new_gc = body(inner, (gp, gc))
                return (inner, {pk: paged.planes[pk] for pk in gkeys}), new_gc
            ((x, aux_total), gout), new_gcaches = jax.lax.scan(
                sweep_body,
                ((x, aux_total), {pk: paged.planes[pk] for pk in gkeys}),
                (params["groups"], caches["groups"],
                 jnp.arange(cfg.num_groups, dtype=jnp.int32)))
            for pk in gkeys:
                paged.planes[pk] = gout[pk]
        else:
            (x, aux_total), new_gcaches = jax.lax.scan(
                body, (x, aux_total), (params["groups"], caches["groups"]))
        new_caches = {"groups": new_gcaches}
        if cfg.first_dense_layers:
            new_caches["dense"] = new_dense
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, new_caches, aux_total


# --------------------------------------------------------------------------
# entry points
# --------------------------------------------------------------------------
AUX_COEF = 0.01


def loss_fn(params, batch, *, cfg: ModelConfig):
    """batch: tokens (B,S), targets (B,S), loss_mask (B,S)
    [+ frontend_embeds (B,Tf,D)]. Returns scalar mean NLL (+ MoE aux)."""
    x, positions = _embed_inputs(params, batch, cfg)
    x, _, aux = backbone(params, x, positions, cfg=cfg, step_kind="train")
    if cfg.frontend is not None and "frontend_embeds" in batch:
        x = x[:, batch["frontend_embeds"].shape[1]:, :]
    nll = L.chunked_xent(params["embedding"], x, batch["targets"],
                         batch["loss_mask"], cfg=cfg)
    return nll + AUX_COEF * aux


def prefill_fn(params, batch, *, cfg: ModelConfig, max_seq=None):
    """Returns (last-token logits (B,V), caches). `max_seq` pre-sizes the
    emitted caches for the decode phase (serving engine contract).

    With right-padded prompts the batch may carry per-slot `lengths` (B,);
    logits are then gathered at each row's last REAL token instead of the
    (padded) final position. The emitted cache `idx` leaves still read T —
    a paged engine overwrites them with the true per-slot lengths."""
    x, positions = _embed_inputs(params, batch, cfg)
    x, caches, _ = backbone(params, x, positions, cfg=cfg, step_kind="prefill",
                            max_seq=max_seq)
    if "lengths" in batch:
        last = jnp.clip(batch["lengths"].astype(jnp.int32) - 1, 0,
                        x.shape[1] - 1)
        xl = x[jnp.arange(x.shape[0]), last][:, None, :]
    else:
        xl = x[:, -1:, :]
    logits = L.logits_apply(params["embedding"], xl, cfg=cfg)
    return logits[:, 0, :], caches


def decode_fn(params, batch, caches, *, cfg: ModelConfig):
    """batch: tokens (B,1), pos0 (B,) absolute position of the new token.
    Returns (logits (B,V), new caches)."""
    x, positions = _embed_inputs(params, batch, cfg)
    x, new_caches, _ = backbone(params, x, positions, cfg=cfg,
                                step_kind="decode", caches=caches)
    logits = L.logits_apply(params["embedding"], x, cfg=cfg)
    return logits[:, 0, :], new_caches


def paged_decode_fn(params, batch, caches, planes, *, cfg: ModelConfig,
                    pul_distance: int = 4):
    """Paged decode step as one sweep: attention reads KV pages directly.

    batch: tokens (B,1), pos0 (B,) absolute position of the new token,
    page_table (B, n_pages) int32 physical frame of each slot's logical
    page, frames/offsets (B,) int32 each slot's tail-page commit position.

    `planes` is the `KVStoreLayout` plane dict. One launch sequence walks
    all layers inside the decode scan over the FULL per-layer planes: each
    layer's pages are read by the sweep kernel at an SMEM layer scalar
    (zero-copy: no per-layer gather/slice is built under jit) and the
    current token's K/V rows are committed by the kernel's fused epilogue
    at batch["frames"]/batch["offsets"]. `caches` carries only non-pageable
    state (SSM leaves, idx); its pageable leaves are placeholders, of which
    only the tree position is used. Returns (logits (B,V), new_caches,
    new_planes).

    `pul_distance` is the preload distance of the in-kernel page ring
    (static; the engine passes the planner's d*)."""
    from repro.core import PULConfig
    x, positions = _embed_inputs(params, batch, cfg)
    paged = L.PagedSweep(
        batch["page_table"].astype(jnp.int32),
        PULConfig(distance=pul_distance),
        jnp.asarray(batch["frames"], jnp.int32),
        jnp.asarray(batch["offsets"], jnp.int32), dict(planes))
    x, new_caches, _ = backbone(params, x, positions, cfg=cfg,
                                step_kind="paged_decode", caches=caches,
                                paged=paged)
    logits = L.logits_apply(params["embedding"], x, cfg=cfg)
    return logits[:, 0, :], new_caches, paged.planes
