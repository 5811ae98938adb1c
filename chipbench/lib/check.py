"""Whether what the timed path served is correct.

Once the window has closed, a sample of the finished requests, drawn from
the seed and holding the one with the most served tokens, goes through the
plain reference (the module the configuration names, ``lib.reference`` by
default): one full-sequence float32 forward over each prompt and its
served tokens.  For every served token the reference
gives the gap by which that token's logit lies below its best logit at that
position; greedy decoding that agrees with the reference to rounding keeps
the gap small.  A configuration's file names the numbers compared and
their limits (``check``): the widest gap over the sample
(``logit_gap``), or its mean over the sampled tokens (``mean_logit_gap``).

The control (``control=True``) ranks the same positions with the reference
computed on float8 weights instead, and reports the reference's gap of the
token the control puts first: what a program serving in the precision below
its configuration's would show.
"""
from __future__ import annotations

import math
import random

import numpy as np

#: a sample holds at least SAMPLE_MIN requests and SAMPLE_TOKENS served
#: tokens, and at most SAMPLE_MAX requests: the longest answer alone can
#: hold the tokens, and would leave the check to one slot and one sequence
SAMPLE_MIN = 4
SAMPLE_TOKENS = 384
SAMPLE_MAX = 8
#: padded sequence lengths are multiples of this (fewer reference compiles)
SEQ_STEP = 256


def sample(finished: list, seed: int) -> list:
    """The longest finished request, then others in an order drawn from
    the seed, until the sample holds SAMPLE_MIN requests and SAMPLE_TOKENS
    served tokens."""
    if not finished:
        return []
    longest = max(finished, key=lambda s: (len(s.tokens), s.rid))
    rest = [s for s in finished if s is not longest]
    random.Random(f"chipbench-sample-{seed}").shuffle(rest)
    out = [longest]
    for s in rest:
        if len(out) >= SAMPLE_MAX or (
                len(out) >= SAMPLE_MIN
                and sum(len(x.tokens) for x in out) >= SAMPLE_TOKENS):
            break
        out.append(s)
    return out


def _padded(n: int, step: int) -> int:
    return step * math.ceil(n / step)


def gaps(weights: dict, spec, requests: list, *, control: bool = False,
         out_rows: int) -> list[np.ndarray]:
    """Per request, the reference's gap (best logit minus the logit of the
    served token, or with ``control`` of the control's first choice) at
    each served position.  ``out_rows`` pads the positions read, so every
    request runs one compiled shape per padded length."""
    logits_at = spec.reference.logits_at
    out = []
    for s in requests:
        prompt = list(s.engine_req.prompt)
        served = s.tokens
        seq = prompt + served[:-1]          # the last token is never fed
        rows = [len(prompt) - 1 + j for j in range(len(served))]
        tokens = np.zeros(_padded(len(seq), SEQ_STEP), np.int32)
        tokens[:len(seq)] = seq
        idx = np.full(out_rows, rows[-1], np.int32)
        idx[:len(rows)] = rows
        ref = np.asarray(logits_at(weights, spec, tokens, idx),
                         np.float64)[:len(rows)]
        best = ref.max(-1)
        if control:
            low = np.asarray(logits_at(weights, spec, tokens, idx,
                                       quant=True))[:len(rows)]
            pick = low.argmax(-1)
        else:
            pick = np.asarray(served)
        out.append(best - ref[np.arange(len(rows)), pick])
    return out


def widest_gap(per_request: list[np.ndarray]) -> float:
    return float(max(g.max() for g in per_request))


def mean_gap(per_request: list[np.ndarray]) -> float:
    """The gap averaged over every sampled served token: steadier from seed
    to seed than the widest, which a mixture of experts sets by the rare
    token whose k-th and (k+1)-th experts nearly tie under rounding."""
    return float(np.concatenate(per_request).mean())


#: the numbers a configuration's ``check`` may compare, by name
NUMBERS = {"logit_gap": widest_gap, "mean_logit_gap": mean_gap}


def evaluate(per_request: list, limits: dict) -> dict:
    """name -> (value, limit) for each number a configuration compares; the
    value is None where no finished request was sampled."""
    return {name: (NUMBERS[name](per_request) if per_request else None,
                   limit) for name, limit in limits.items()}


def correct(checks: dict) -> bool:
    return all(v is not None and v <= lim for v, lim in checks.values())
