"""Operations and least bytes of a dense decoder's steps, from its shapes.

Computed from the configuration file's sizes alone, so that the same
formula holds whatever implements the step.  A configuration uses the
source's key names (``hidden_size``, ``num_key_value_heads``, ...).
"""
from __future__ import annotations


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    nq = cfg["num_attention_heads"]
    nkv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // nq
    return d, nq, nkv, hd


def layer_params(cfg: dict) -> int:
    """Weights of one layer that every token multiplies by: attention
    projections, the gated MLP and two norms."""
    d, nq, nkv, hd = _dims(cfg)
    attn = d * nq * hd + 2 * d * nkv * hd + nq * hd * d
    return attn + 3 * d * cfg["intermediate_size"] + 2 * d


def matmul_params(cfg: dict) -> int:
    """Weights every token is multiplied by: all layers, the final norm and
    the output head (the embedding is a lookup, not a product)."""
    d = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * layer_params(cfg) + d
            + d * cfg["vocab_size"])


def attended(cfg: dict, position: int) -> int:
    """Positions a token at 0-based ``position`` attends to."""
    w = cfg.get("sliding_window") or 0
    n = position + 1
    return min(n, w) if w else n


def token_flops(cfg: dict, position: int) -> float:
    """Model operations of one token: 2 per weight multiplied, and
    ``QK^T`` plus ``PV`` over the positions it attends to."""
    _, nq, _, hd = _dims(cfg)
    attn = 4 * nq * hd * attended(cfg, position) * cfg["num_hidden_layers"]
    return 2.0 * matmul_params(cfg) + attn


def sequence_flops(cfg: dict, start: int, count: int) -> float:
    """Operations of ``count`` tokens at positions ``start ..``."""
    return sum(token_flops(cfg, p) for p in range(start, start + count))


def decode_least_bytes(cfg: dict, rows: int, ctx: int,
                       bytes_per: int = 2) -> float:
    """Least bytes one decode step of ``rows`` rows must move, with ``ctx``
    cache positions valid after it has written its token: every weight it
    multiplies by, the rows' embeddings, and the keys and values of the
    positions each row attends to."""
    d, _, nkv, hd = _dims(cfg)
    kv = rows * attended(cfg, ctx - 1) * 2 * nkv * hd * \
        cfg["num_hidden_layers"]
    return bytes_per * (matmul_params(cfg) + rows * d + kv)
