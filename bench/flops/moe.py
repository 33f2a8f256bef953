"""Required FLOPs of a decoder-only transformer with grouped-query
attention and a top-k mixture of SwiGLU experts.

Per token and layer: the q/k/v/o projections ``2 d (Hq + 2 Hkv) hd + 2 Hq
hd d``, the router ``2 d E``, and only the ``top_k`` experts the token is
routed to, ``top_k * 3 * 2 d f`` (not all ``E``: a program that runs
every expert executes more than this). Attention over ``ctx`` keys costs
``4 Hq hd ctx`` (scores and the weighted sum of values), so a prompt of
``L`` tokens pays ``4 Hq hd L (L + 1) / 2`` per layer with causal
masking. Norms, RoPE, softmax and activations are left out (under 1%).
The unembedding ``2 d V`` is counted once per token whose logits are
needed: every decoded token, and only the last token of a prompt.
"""
from __future__ import annotations


def _dims(config):
    d = config["d_model"]
    hq, hkv = config["n_heads"], config["n_kv_heads"]
    hd = config.get("head_dim") or d // hq
    return d, hq, hkv, hd


def layer_flops(config) -> float:
    """Per token and layer, without attention over the context."""
    d, hq, hkv, hd = _dims(config)
    m = config["moe"]
    proj = 2 * d * (hq + 2 * hkv) * hd + 2 * hq * hd * d
    router = 2 * d * m["n_experts"]
    experts = m["top_k"] * 3 * 2 * d * m["d_expert"]
    return float(proj + router + experts)


def attn_flops(config, ctx: float) -> float:
    """Per token and layer, attending over ``ctx`` keys."""
    d, hq, hkv, hd = _dims(config)
    return 4.0 * hq * hd * ctx


def head_flops(config) -> float:
    return 2.0 * config["d_model"] * config["vocab"]


def prompt_flops(config, length: int) -> float:
    n = config["n_layers"]
    return length * n * layer_flops(config) \
        + n * attn_flops(config, length * (length + 1) / 2) \
        + head_flops(config)


def decode_flops(config, ctx: int) -> float:
    """One decoded token at position ``ctx - 1`` (``ctx`` keys)."""
    n = config["n_layers"]
    return n * (layer_flops(config) + attn_flops(config, ctx)) \
        + head_flops(config)
