"""The ``ssd_extend`` kernel (``repro.kernels.ssd_scan``): the SSD
recurrence run token by token from a given state, over ``b`` rows of
``T`` tokens, ``h`` heads of width ``p``, ``g`` groups of state size
``n``.

Required work of one call, from its shapes:

* FLOPs ``b T h (5 p n + 3 p)``: per token and head the state update
  (decay multiply, input outer product, accumulate: ``3 p n``), the
  readout (``2 p n``), and ``dt x`` plus the ``D`` skip (``3 p``).
  Elementwise float32 work, held against the chip's bf16 peak as every
  kernel is.
* Bytes ``4 (2 b h p n + 2 b T h p + b T h + 2 b T g n)``: the float32
  state read and written once, ``x`` in and ``y`` out, ``dt``, and
  ``B``/``C``. A layout that moves more (``dt`` spread over 128 lanes,
  ``B``/``C`` read once per head) costs the kernel, not the yardstick.

The program gives its Pallas calls no name yet, so a call is found in the
trace by its signature: a ``tpu_custom_call`` taking ``(state, x, dt, A,
B, C, D)`` and returning ``(y, state)``, with ``y`` shaped as ``x`` and
the new state as the old.
"""
from __future__ import annotations

import re
from typing import Optional, Tuple

Shape = Tuple[int, int, int, int, int, int]
_F32 = re.compile(r"f32\[([0-9,]*)\]")


def _dims(text: str):
    return [tuple(int(v) for v in m.split(",") if v)
            for m in _F32.findall(text)]


def shapes(event_name: str) -> Optional[Shape]:
    """``(b, T, h, p, g, n)`` of an ``ssd_extend`` call from its trace
    event (the HLO instruction's text), or None for any other op."""
    if 'custom_call_target="tpu_custom_call"' not in event_name:
        return None
    lhs, _, rest = event_name.partition(" custom-call(")
    outs = _dims(lhs.partition(" = ")[2])
    ins = _dims(rest.partition("), custom_call_target")[0])
    if len(outs) != 2 or len(ins) != 7:
        return None
    state, x, dt, A, B, C, D = ins
    if not (len(state) == len(x) == len(B) == 4 and outs == [x, state]
            and B == C and len(A) == 1):
        return None
    b, h, T, p = x
    g, n = B[1], B[3]
    return (b, T, h, p, g, n)


def flops(b, T, h, p, g, n) -> float:
    return float(b * T * h * (5 * p * n + 3 * p))


def bytes_moved(b, T, h, p, g, n) -> float:
    return 4.0 * (2 * b * h * p * n + 2 * b * T * h * p + b * T * h
                  + 2 * b * T * g * n)
