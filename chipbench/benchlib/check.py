"""Decide ``correct``: served tokens against the plain reference.

Once the window has closed and the engine is gone, a sample of the
finished requests, drawn from the seed and holding the longest one, is
scored by the reference over each prompt with its served tokens.  The
number compared is the widest gap by which a served token's reference
logit lies below the reference's best, in standard deviations of that
position's reference logits (greedy serving puts the engine's best first).
The control puts the float8 forward in the program's place: at each
position of the same prompts and served tokens, the token it puts first is
scored the same way.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchlib import reference


def sample(records, seed: int, min_tokens: int, min_requests: int) -> List:
    """The longest finished request, then others in an order the seed
    draws, until the sample holds ``min_requests`` requests and
    ``min_tokens`` served tokens."""
    done = [r for r in records if r.result is not None and r.result.tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.result.tokens))
    rest = [r for r in done if r is not longest]
    picked, n = [longest], len(longest.result.tokens)
    for i in np.random.default_rng(seed).permutation(len(rest)):
        if n >= min_tokens and len(picked) >= min_requests:
            break
        picked.append(rest[i])
        n += len(rest[i].result.tokens)
    return picked


def compare(weights, arch: dict, picked, control: bool = False
            ) -> Dict[str, float]:
    """The widest served-token gap over the sample (``token_gap``) and,
    with ``control``, the widest gap of the tokens the float8 forward puts
    first at the same positions (``control_gap``)."""
    out = {"token_gap": 0.0, "tokens_compared": 0}
    if control:
        out["control_gap"] = 0.0
    for r in picked:
        served = r.result.tokens
        gaps = reference.token_gaps(weights, arch, r.prompt, served)
        out["token_gap"] = max(out["token_gap"], float(gaps.max()))
        out["tokens_compared"] += len(served)
        if control:
            cg = reference.token_gaps(weights, arch, r.prompt, served,
                                      control=True)
            out["control_gap"] = max(out["control_gap"], float(cg.max()))
    return out
