"""Arithmetic of the end-to-end metrics, over every request of a window.

Each request is a ``Served`` record: when it was due and when each of its
output tokens reached the client, on one clock.  A failed request has no
tokens.  Tails are taken over all requests due in the window; a failed
request counts as missing, by a time to first token that runs to the end
of the run, so it lies in the tail.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np


@dataclass
class Served:
    due: float
    prompt_len: int = 0
    token_times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    failed: bool = False
    t_enqueued: Optional[float] = None


def percentile(values, q: float) -> float:
    """The ``q``-th percentile, linear between order statistics."""
    if len(values) == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttfts(reqs: List[Served], run_end: float) -> List[float]:
    return [(run_end if r.failed or not r.token_times else r.token_times[0])
            - r.due for r in reqs]


def inter_token_gaps(reqs: List[Served]) -> List[float]:
    out: List[float] = []
    for r in reqs:
        t = r.token_times
        out.extend(b - a for a, b in zip(t, t[1:]))
    return out


def tokens_per_s(reqs: List[Served], window_start: float) -> float:
    """All output tokens of the window's requests over the time from the
    window's start until the last of them finished."""
    done = [r for r in reqs if r.token_times]
    if not done:
        return 0.0
    end = max(r.token_times[-1] for r in done)
    return sum(len(r.token_times) for r in done) / (end - window_start)


def end_to_end(reqs: List[Served], window_start: float,
               run_end: float) -> dict:
    """Every end-to-end metric the harness can compute, by name."""
    gaps = inter_token_gaps(reqs)
    return {
        "tokens_per_s": tokens_per_s(reqs, window_start),
        "itl_p95_ms": percentile(gaps, 95) * 1e3 if gaps else None,
        "ttft_p90_s": percentile(ttfts(reqs, run_end), 90),
    }
