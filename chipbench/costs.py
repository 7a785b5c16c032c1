"""Operations and bytes a dense GQA decoder needs, from its configuration.

Model FLOPs count each multiply-add as 2 and only the work the answer
needs: the projections, the MLP and causal attention over the positions
already written, plus the output head once for each token whose logits are
used (a prompt's last position and every generated token). Recomputation,
padding, masked blocks and empty slots do not count.
"""
from __future__ import annotations

BF16 = 2


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
