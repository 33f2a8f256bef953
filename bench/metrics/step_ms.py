"""Engine layer: host wall time of the traced ticks over the engine steps
they ran (``Engine.tick`` counts its steps), in ms per step. A tick's
wall includes its admission pass and poll, so host work per step shows
here."""
from __future__ import annotations


def read(ctx):
    steps = sum(t.steps for t in ctx.ticks)
    if not steps:
        return None
    return 1e3 * sum(t.end - t.start for t in ctx.ticks) / steps
