"""Decide ``correct``: the tokens the timed path served, held against the
plain float32 reference.

After the window closes (and after the program's state is freed), a
sample drawn from the seed of the requests the window finished — always
with the longest of them — goes through the reference: each request's
prompt plus its served tokens, teacher-forced. For each served token
the number read is how far its reference logit lies below the
reference's best logit at that position (0 when the served token is the
reference's own greedy choice). The number compared is the widest such
gap over the sample; greedy serving at the configuration's precision
keeps it small, a wrong token makes it large.

The control (``mode="fp8"``, not run by the benchmark's own runs) puts
the reference computed in float8 in the program's place and reads, at the
same positions, the gap of the token float8 puts first.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from bench.traffic import common

BUCKET = 1024


def sample(records: Sequence, k: int, seed: int) -> List:
    """The longest finished request plus ``k - 1`` others drawn from the
    seed (all of them when fewer finished)."""
    done = sorted(records, key=lambda r: r.uid)
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + r.n, -r.uid))
    rest = [r for r in done if r is not longest]
    rng = common.rng_for(seed, 4)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False) \
        if rest and k > 1 else []
    return [longest] + [rest[i] for i in sorted(pick)]


def _inputs(recs, cap: int):
    """Teacher-forced inputs for a group of requests (prompt + served
    tokens but the last), padded to a common multiple of ``BUCKET`` (at
    most ``cap``), and for each the positions whose logits predict its
    served tokens (padded by repeating the last)."""
    seqs = [np.concatenate([r.prompt, r.tokens[:-1]]).astype(np.int32)
            for r in recs]
    longest = max(len(q) for q in seqs)
    length = max(longest, min(cap, -(-longest // BUCKET) * BUCKET))
    tokens = np.zeros((len(recs), length), np.int32)
    width = max(len(r.tokens) for r in recs)
    read = np.zeros((len(recs), width), np.int32)
    for i, (r, q) in enumerate(zip(recs, seqs)):
        tokens[i, :len(q)] = q
        pos = np.arange(r.prompt_len - 1, r.prompt_len - 1 + len(r.tokens))
        read[i] = np.concatenate([pos, np.full(width - len(pos), pos[-1])])
    return tokens, read


def gaps(ref, params, config, recs, cap: int, control: bool = False
         ) -> Dict[str, np.ndarray]:
    """Per served token of each request: the reference gap of the served
    token and, with ``control``, the reference gap of the float8 forward's
    first choice. Requests go to the reference ``ref.ROWS`` at a time.
    Returns arrays concatenated over the sample."""
    import jax.numpy as jnp
    served, ctrl = [], []
    rows = ref.ROWS
    for i in range(0, len(recs), rows):
        group = recs[i:i + rows]
        tokens, read = _inputs(group, cap)
        lg = ref.logits(params, config, tokens, read, mode="f32")
        best = jnp.max(lg, axis=-1)
        pad = np.zeros(read.shape, np.int32)
        for j, r in enumerate(group):
            pad[j, :len(r.tokens)] = r.tokens
        at = jnp.take_along_axis(lg, jnp.asarray(pad)[..., None], -1)[..., 0]
        gap = np.asarray(best - at)
        served += [gap[j, :len(r.tokens)] for j, r in enumerate(group)]
        if control:
            pick = jnp.argmax(ref.logits(params, config, tokens, read,
                                         mode="fp8"), axis=-1)
            at_c = jnp.take_along_axis(lg, pick[..., None], -1)[..., 0]
            gap_c = np.asarray(best - at_c)
            ctrl += [gap_c[j, :len(r.tokens)] for j, r in enumerate(group)]
    out = {"served": np.concatenate(served) if served else np.zeros(0)}
    if control:
        out["control"] = np.concatenate(ctrl) if ctrl else np.zeros(0)
    return out


def verdict(readings: Dict[str, float], limits: Dict) -> tuple:
    """``(correct, checks)``: each number compared beside its limit.
    ``max_logit_gap`` must stay at or under its limit, and the sample must
    hold at least ``min_tokens`` served tokens."""
    checks = {
        "max_logit_gap": {"value": readings["max_logit_gap"],
                          "limit": limits["max_logit_gap"]["limit"]},
        "tokens_checked": {"value": readings["tokens_checked"],
                           "limit": limits["min_tokens"]},
    }
    ok = (readings["max_logit_gap"] <= limits["max_logit_gap"]["limit"]
          and readings["tokens_checked"] >= limits["min_tokens"])
    return bool(ok), checks
