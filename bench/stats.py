"""Tail arithmetic over the run's request records.

Every tail is the tail of all requests due in the window (or, for
inter-token gaps, of all gaps that end in it), never a median of
chunks. A request with no first token (or no last token) when the
window closes counts at the age it had reached, so a stall shows.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np


def p95(values: Sequence[float]) -> Optional[float]:
    """95th percentile (linear interpolation), None for no samples."""
    return float(np.percentile(np.asarray(values, float), 95)) \
        if len(values) else None


def due_in_window(log) -> List:
    return [r for r in list(log.reqs) + list(log.unsubmitted)
            if log.open_t <= r.due < log.close_t]


def ttft_samples(log) -> List[float]:
    """Due time to first token; the age at the close if none came."""
    out = []
    for r in due_in_window(log):
        first = r.first_t
        out.append((first if first is not None and first < log.close_t
                    else log.close_t) - r.due)
    return out


def turn_samples(log) -> List[float]:
    """Due time to last token; the age at the close if not finished."""
    out = []
    for r in due_in_window(log):
        fin = r.finish_t
        out.append((fin if fin is not None and fin < log.close_t
                    else log.close_t) - r.due)
    return out


def itl_samples(log) -> List[float]:
    """Gaps between consecutive output tokens of one request, for every
    gap whose later token lands in the window."""
    out = []
    for r in log.reqs:
        st = r.stamps
        for a, b in zip(st, st[1:]):
            if log.open_t <= b < log.close_t:
                out.append(b - a)
    return out


def in_flight(log, t: float) -> int:
    """Requests due by ``t`` and not finished by then."""
    return sum(1 for r in list(log.reqs) + list(log.unsubmitted)
               if r.due <= t and (r.finish_t is None or r.finish_t > t))


def tokens_in_window(log) -> int:
    return sum(1 for r in log.reqs for t in r.stamps
               if log.open_t <= t < log.close_t)


def lateness(log) -> List[float]:
    """How late each request due in the window was handed to the
    system (the generator shares the host with the server)."""
    return [r.submit_t - r.due for r in log.reqs
            if r.submit_t is not None and log.open_t <= r.due < log.close_t]


def summary(log) -> Dict[str, float]:
    """The sample counts printed on an earlier line."""
    due = due_in_window(log)
    return {
        "due_in_window": len(due),
        "first_token_in_window": sum(
            1 for r in due if r.first_t is not None
            and r.first_t < log.close_t),
        "finished_in_window": sum(
            1 for r in due if r.finish_t is not None
            and r.finish_t < log.close_t),
        "never_submitted": sum(1 for r in due if r.index < 0),
        "itl_gaps": len(itl_samples(log)),
        "ttft_p50_ms": 1e3 * float(np.median(ttft_samples(log) or [0.0])),
        "tokens_in_window": tokens_in_window(log),
        "late_p95_ms": 1e3 * (p95(lateness(log)) or 0.0),
        "late_max_ms": 1e3 * max(lateness(log), default=0.0),
    }
