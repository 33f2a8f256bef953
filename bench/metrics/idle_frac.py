"""Device layer: the share of the time inside ticks that ran at least one
step during which no operation ran on the device, in % (from the trace:
1 - busy union / tick time). Taken inside working ticks, so an open loop
that waits for arrivals does not read as idle."""
from __future__ import annotations


def read(ctx):
    red = ctx.red
    if red is None or red.tick_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_in_ticks_s / red.tick_s)
