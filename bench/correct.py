"""How ``correct`` is decided: what the timed path served, against the
plain reference (bench/reference.py).

Once the window has closed and the program's state is freed, a sample of
the requests the run finished, drawn from the seed and always holding
the longest, is run through the reference: each request's prompt with
its served tokens, once. For every served token -- the request's own,
and those of its session's earlier turns that its prompt carries -- the
gap by which its reference logit lies below the reference's best is
read. Tokens are greedy, so a sound program's gaps are rounding; the
widest gap is held to the configuration's limit.

The control takes the program's place in the same comparison: the
reference computed in int8, the step below the configuration's
bfloat16, teacher-forced over the same sequences. The tokens it puts
first at every position are judged against the same float32 reference
and the same limit, and ``correct`` has to come out false.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench import reference

#: served tokens the sample has to reach (plus the longest request)
SAMPLE_TOKENS = 320
SAMPLE_MAX_REQUESTS = 8


def targets_of(req, reqs) -> List[Tuple[int, int]]:
    """(position whose logits predict the token, token) for every served
    token in ``req``'s sequence: its own output and the outputs of its
    session's earlier turns, which its prompt carries."""
    out = []
    turns = [r for r in reqs if r.session == req.session
             and r.turn <= req.turn and r.output]
    for r in turns:
        n = len(r.prompt)
        out.extend((n - 1 + j, tok) for j, tok in enumerate(r.output))
    return out


def sample(reqs, seed: int) -> List:
    """The longest finished request, then others drawn from the seed
    until the served tokens reach ``SAMPLE_TOKENS``."""
    done = [r for r in reqs if r.finish_t is not None and r.output]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.output),
                                       r.index))
    rng = np.random.default_rng([seed, 0xC0DE])
    picked, tokens = [longest], len(targets_of(longest, reqs))
    for j in rng.permutation(len(done)):
        r = done[j]
        if tokens >= SAMPLE_TOKENS or len(picked) >= SAMPLE_MAX_REQUESTS:
            break
        if r is not longest:
            picked.append(r)
            tokens += len(targets_of(r, reqs))
    return picked


def judge(weights, shape, reqs, seed: int, buf_len: int, limit: float,
          control: bool = False) -> Dict:
    """Run the reference over the sample; returns the verdict, the
    widest gap and the counts. With ``control`` the int8 control's
    tokens are judged in place of the served ones, and the program's
    own widest gap is returned beside them as ``program_gap``."""
    picked = sample(reqs, seed)
    worst = ctrl = 0.0
    checked = 0
    for r in picked:
        seq = r.prompt + r.output[:-1]
        tg = targets_of(r, reqs)
        g, c = reference.sequence_gaps(weights, shape, seq, tg, buf_len,
                                       control=control)
        worst, ctrl = max(worst, g), max(ctrl, c)
        checked += len(tg)
    gap = ctrl if control else worst
    out = {"correct": bool(picked) and gap <= limit, "logit_gap": gap,
           "limit": limit, "requests_checked": len(picked),
           "tokens_checked": checked,
           "longest_checked": max((len(r.prompt) + len(r.output)
                                   for r in picked), default=0)}
    if control:
        out["program_gap"] = worst
    return out
