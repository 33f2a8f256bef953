"""Required FLOPs of a Mamba-2 (SSD) language model.

Per token and layer: the input projection ``2 d (2 d_in + 2 G N + H)``,
the output projection ``2 d_in d``, the depthwise conv ``2 K (d_in + 2 G
N)``, and the recurrence ``5 H P N`` (decay multiply, input outer
product and accumulate on the state: 3; readout multiply-add: 2) plus
``3 H P`` (``dt x`` and the ``D`` skip). Norms, gates and activations are
left out (elementwise, under 1% of the total). The unembedding ``2 d V``
is counted once per token whose logits are needed: every decoded token,
and only the last token of a prompt. The recurrent form costs the same at
any context length.
"""
from __future__ import annotations


def layer_flops(config) -> float:
    s = config["ssm"]
    d = config["d_model"]
    d_in = s["expand"] * d
    H = d_in // s["head_dim"]
    P, N, G, K = s["head_dim"], s["d_state"], s["n_groups"], s["d_conv"]
    proj = 2 * d * (2 * d_in + 2 * G * N + H) + 2 * d_in * d
    conv = 2 * K * (d_in + 2 * G * N)
    scan = 5 * H * P * N + 3 * H * P
    return float(proj + conv + scan)


def head_flops(config) -> float:
    return 2.0 * config["d_model"] * config["vocab"]


def prompt_flops(config, length: int) -> float:
    """Admitting a prompt of ``length`` tokens: every token through every
    layer, logits for the last."""
    return length * config["n_layers"] * layer_flops(config) \
        + head_flops(config)


def decode_flops(config, ctx: int) -> float:
    """One decoded token (``ctx``, its position + 1, does not matter)."""
    return config["n_layers"] * layer_flops(config) + head_flops(config)
