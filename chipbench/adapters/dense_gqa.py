"""The dense GQA family in the program's terms: its model configuration
built from the configuration file, and the benchmark's weights
(`reference/dense_gqa.py` `shapes`) placed in its parameter tree.
"""
from __future__ import annotations

import dataclasses

import chipbench.adapter  # noqa: F401  (puts the program's src/ on sys.path)
from repro.configs import get_config


def model_config(name: str, arch: dict):
    """The program's config of registry entry `name`, with every size and
    option taken from the configuration file `arch`."""
    base = get_config(name)
    cfg = dataclasses.replace(
        base,
        num_layers=arch["num_hidden_layers"],
        d_model=arch["hidden_size"],
        num_heads=arch["num_attention_heads"],
        num_kv_heads=arch["num_key_value_heads"],
        head_dim=arch["head_dim"],
        d_ff=arch["intermediate_size"],
        vocab_size=arch["vocab_size"],
        norm_eps=arch["rms_norm_eps"],
        rope_theta=float(arch["rope_theta"]),
        tie_embeddings=arch["tie_word_embeddings"],
        qkv_bias=arch["qkv_bias"],
        qk_norm=arch["qk_norm"],
        vocab_chunk=arch.get("vocab_chunk", base.vocab_chunk))
    if (cfg.family, cfg.attn_type, cfg.pattern, cfg.mlp_type) != (
            "dense", "gqa", ("global",), "swiglu"):
        raise ValueError(f"{name}: not a dense GQA SwiGLU decoder")
    return cfg


def params(cfg, w: dict) -> dict:
    """The benchmark's weights `w` in the program's parameter tree. The
    program's RMSNorm scales by (1 + s), so a norm weight g is handed over
    as g - 1; matmul weights are the same buffers."""
    one = lambda g: g - 1.0
    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo")}
    if cfg.qkv_bias:
        attn.update(bq=w["bq"], bk=w["bk"], bv=w["bv"])
    if cfg.qk_norm:
        attn.update(q_norm=one(w["q_norm"]), k_norm=one(w["k_norm"]))
    tree = {
        "embedding": {"table": w["embed"]},
        "final_norm": one(w["final_norm"]),
        "groups": {"0:global": {
            "norm1": one(w["attn_norm"]), "norm2": one(w["mlp_norm"]),
            "attn": attn,
            "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")}}},
    }
    if not cfg.tie_embeddings:
        tree["embedding"]["lm_head"] = w["lm_head"]
    return tree
