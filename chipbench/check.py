"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the finished requests, drawn from
the seed and always holding the one with the most served tokens, is run
through the configuration's plain reference once, over each prompt with
its served tokens.  The number compared is the widest gap by which a
served token's reference logit lies below the reference's best logit at
its position.  Served tokens are greedy, so a sound program reads a gap of
rounding size only.
"""
from __future__ import annotations

import importlib
from typing import List, Sequence

import numpy as np


def sample(n_tokens: Sequence[int], seed: int, count: int) -> List[int]:
    """Indices of ``count`` finished requests: the one with the most
    served tokens, then others drawn from ``seed``."""
    done = [i for i, n in enumerate(n_tokens) if n > 0]
    if not done:
        return []
    longest = max(done, key=lambda i: (n_tokens[i], -i))
    rest = [i for i in done if i != longest]
    rng = np.random.default_rng([int(seed), 3])
    return [longest] + [rest[j] for j in
                        rng.permutation(len(rest))[:count - 1]]


def max_length(spec: dict) -> int:
    return int(spec["value"] if spec["dist"] == "fixed" else spec["max"])


def reference_module(cfg: dict):
    return importlib.import_module(f"chipbench.reference.{cfg['reference']}")


def gaps(cfg: dict, seed: int, prompts: List[np.ndarray],
         served: List[List[int]], controls=(), shape=(0, 0, 0)):
    """Gaps, one array per request, of the served tokens under the
    reference; then, for each control, of the tokens the control puts
    first at the same positions of the same sequences.  ``shape`` gives
    the least (sequences, positions, served tokens) the reference is run
    at, so that every run of a cell runs it at one shape."""
    seqs = [np.concatenate([p, np.asarray(s[:-1], p.dtype)])
            for p, s in zip(prompts, served)]
    B = max(len(seqs), shape[0])
    T = max(max(len(s) for s in seqs), shape[1])
    N = max(max(len(s) for s in served), shape[2])
    tokens = np.zeros((B, T), np.int32)
    rows = np.zeros((B, N), np.int32)
    for i, (p, s, q) in enumerate(zip(prompts, served, seqs)):
        tokens[i, :len(q)] = q
        rows[i] = np.minimum(len(p) - 1 + np.arange(N), len(q) - 1)
    out = reference_module(cfg).logits(cfg, seed, tokens, rows,
                                       (None,) + tuple(controls))
    ref = out[0]
    best = ref.max(axis=-1)
    result = []
    for picks in [None] + list(out[1:]):
        per = []
        for i, s in enumerate(served):
            n = len(s)
            tok = (np.asarray(s) if picks is None
                   else picks[i, :n].argmax(axis=-1))
            per.append(best[i, :n] - ref[i, np.arange(n), tok])
        result.append(per)
    return result


def widest(per_request: List[np.ndarray]) -> float:
    return float(max(np.max(g) for g in per_request))
