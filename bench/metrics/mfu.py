"""Model-step layer: the FLOPs the traced ticks required (``flops/<family>``:
each prompt admitted, counted when its first token is harvested, and each
decoded token at its own context length) over the ticks' wall time times
the chip's peak bf16 rate, in %. Executed work beyond the required (all
experts for every token, padded chunks) does not count."""
from __future__ import annotations


def required_flops(ctx) -> float:
    f, cfg = ctx.flops, ctx.config
    total = 0.0
    for t in ctx.ticks:
        for uid, n0, n1 in t.emits:
            plen = ctx.window.records[uid].prompt_len
            if n0 == 0:
                total += f.prompt_flops(cfg, plen)
            for i in range(max(n0, 1), n1):
                total += f.decode_flops(cfg, plen + i)
    return total


def read(ctx):
    peak = ctx.peaks.get("bf16_flops")
    wall = sum(t.end - t.start for t in ctx.ticks)
    if not peak or wall <= 0:
        return None
    flops = required_flops(ctx)
    return 100.0 * flops / (wall * peak) if flops else None
