"""The one generator of open-loop serving traffic, driven by a traffic file.

A traffic file names an arrival process and the distributions of prompt
and output lengths.  From them this module makes a fixed schedule: ``n =
round(rate * seconds)`` requests whose inter-arrival gaps and lengths are
the ``(i + 0.5) / n`` quantiles of those distributions, put in an order
drawn from the file's own ``schedule_seed``.  The schedule does not depend
on ``--seed``: with waves that last as long as their longest member, the
order of the lengths decides how much work the device does, so a seed that
reordered them would change the work and not only the inputs.  ``--seed``
draws the prompts' tokens (and, elsewhere, the weights).

Arrivals: ``{"process": "gamma", "cv": c, "rate_per_s": r}``; ``cv`` 1 is
a Poisson process, larger is burstier.  Lengths: ``{"dist": "fixed",
"value": v}`` or ``{"dist": "lognormal", "median": m, "sigma": s, "min": a,
"max": b}``.  An optional ``"requests": k`` keeps only the
schedule's first ``k`` requests: a mix offered above the knee then ends
with a fixed amount of work, every wave after the first full, instead of
a queue that grows until the window closes.  ``source`` and ``assumed``
are notes for the reader: where each number comes from.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from typing import List

import numpy as np
from scipy import stats


@dataclass(frozen=True)
class Request:
    index: int
    due_s: float            # seconds after the window's start
    prompt_len: int
    max_new_tokens: int


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles of ``spec``, in ascending order."""
    q = _quantiles(n)
    dist = spec["dist"]
    if dist == "fixed":
        vals = np.full(n, float(spec["value"]))
    elif dist == "lognormal":
        vals = stats.lognorm.ppf(q, spec["sigma"], scale=spec["median"])
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo, hi = spec.get("min", 1), spec.get("max", np.inf)
    return np.clip(np.round(vals), lo, hi).astype(np.int64)


def gaps(spec: dict, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at the quantiles of ``spec``, in ascending
    order, scaled so that they add up to exactly ``n / rate``."""
    if spec["process"] != "gamma":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    rate, cv = float(spec["rate_per_s"]), float(spec["cv"])
    shape = 1.0 / cv ** 2
    g = stats.gamma.ppf(_quantiles(n), shape, scale=1.0 / (rate * shape))
    return g * ((n / rate) / g.sum())


def schedule(traffic: dict, seconds: float) -> List[Request]:
    """The fixed schedule of a window of ``seconds``: every request is due
    inside it, the last at exactly ``n / rate`` seconds; with ``requests``
    in the file, only the first that many of them."""
    rate = float(traffic["arrivals"]["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(int(traffic["schedule_seed"]))
    due = np.cumsum(rng.permutation(gaps(traffic["arrivals"], n)))
    prompt = rng.permutation(lengths(traffic["prompt_tokens"], n))
    out = rng.permutation(lengths(traffic["output_tokens"], n))
    keep = min(n, int(traffic.get("requests", n)))
    return [Request(i, float(due[i]), int(prompt[i]), int(out[i]))
            for i in range(keep)]


def prompts(requests: List[Request], vocab_size: int, seed: int,
            stream: int = 0) -> List[np.ndarray]:
    """Each request's prompt tokens, uniform over ``[1, vocab_size)``,
    drawn from ``seed`` (any non-negative integer) and ``stream``."""
    rng = np.random.default_rng([int(seed), int(stream)])
    return [rng.integers(1, vocab_size, size=r.prompt_len, dtype=np.int32)
            for r in requests]
