"""Plain reference of a dense decoder: GQA attention with rotary position
embeddings and an optional sliding window, a SiLU-gated MLP, RMSNorm, an
untied output head (the Llama/Mistral layout that H2O-Danube3 and
Mistral-NeMo publish).

Written from the published description in straightforward ``jax.numpy``
and float32, with no cache, batching of requests or kernels, and run at
``highest`` matmul precision.  It imports nothing of the program under test.

Weights are made from the seed by the rule the served weights follow,
so that both sides hold the same numbers: ``key = PRNGKey(seed)``; the
token table is ``0.02 * N(0, 1)`` from ``fold_in(key, 1)``; the output
head ``N(0, 1) / sqrt(d)`` from ``fold_in(fold_in(key, 1), 1)``; layer
``l`` takes ``split(fold_in(key, 17), L)[l]``, split in four, of which
the second (split again in four) gives ``wq, wk, wv, wo`` and the fourth
(split in three) gives ``gate, up, down``, each ``N(0, 1) / sqrt(fan_in)``;
norm scales are 1.  Every weight is rounded to bfloat16, the type it is
served in, and computed with in float32.

A control computes the same in a lower precision than the bfloat16 the
model is served in, to show that the comparison fails it:
``control="int8"`` rounds every weight matrix to int8 per output channel
(symmetric, scale ``max|w| / 127``); ``control="fp8"`` rounds every
weight matrix to float8 e4m3 per output channel and every matrix
product's input to e4m3 per row.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _dims(cfg):
    d = cfg["hidden_size"]
    nq = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // nq
    return d, nq, cfg["num_key_value_heads"], hd, cfg["intermediate_size"]


def _normal(key, shape, std):
    w = jax.random.normal(key, shape, jnp.float32) * std
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def _round_fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _quant(w, control):
    """A weight matrix (in, out) as the control holds it."""
    if control is None:
        return w
    if control == "int8":
        scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    if control == "fp8":
        return _round_fp8(w, 0)
    raise ValueError(f"unknown control {control!r}")


def _mm(x, w, control):
    """``x @ w``; the fp8 control also rounds x, one scale per row."""
    if control == "fp8":
        x = _round_fp8(x, -1)
    return x @ w


def layer_weights(cfg, key, control=None):
    d, nq, nkv, hd, ff = _dims(cfg)
    r = jax.random.split(key, 4)
    a = jax.random.split(r[1], 4)
    m = jax.random.split(r[3], 3)
    w = {"wq": _normal(a[0], (d, nq * hd), d ** -0.5),
         "wk": _normal(a[1], (d, nkv * hd), d ** -0.5),
         "wv": _normal(a[2], (d, nkv * hd), d ** -0.5),
         "wo": _normal(a[3], (nq * hd, d), (nq * hd) ** -0.5),
         "gate": _normal(m[0], (d, ff), d ** -0.5),
         "up": _normal(m[1], (d, ff), d ** -0.5),
         "down": _normal(m[2], (ff, d), ff ** -0.5)}
    return {k: _quant(v, control) for k, v in w.items()}


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x (T, h, hd), positions 0..T-1; rotate the two halves."""
    T, _, hd = x.shape
    inv = theta ** (-(jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv        # T, hd/2
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(cfg, w, x, control=None):
    """One layer over one sequence x (T, d)."""
    d, nq, nkv, hd, _ = _dims(cfg)
    eps = cfg.get("rms_norm_eps", 1e-5)
    T = x.shape[0]
    h = _rms(x, eps)
    q = _rope(_mm(h, w["wq"], control).reshape(T, nq, hd), cfg["rope_theta"])
    k = _rope(_mm(h, w["wk"], control).reshape(T, nkv, hd), cfg["rope_theta"])
    v = _mm(h, w["wv"], control).reshape(T, nkv, hd)
    k = jnp.repeat(k, nq // nkv, axis=1)
    v = jnp.repeat(v, nq // nkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    qi, ki = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = ki <= qi
    window = cfg.get("sliding_window") or 0
    if window:
        mask &= qi - ki < window
    s = jnp.where(mask[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    x = x + _mm(o.reshape(T, nq * hd), w["wo"], control)
    h = _rms(x, eps)
    return x + _mm(jax.nn.silu(_mm(h, w["gate"], control))
                 * _mm(h, w["up"], control), w["down"], control)


@partial(jax.jit, static_argnums=(0, 3))
def _layer_step(cfg_items, key, xs, control):
    cfg = dict(cfg_items)
    w = layer_weights(cfg, key, control)
    return jax.lax.map(lambda x: _layer(cfg, w, x, control), xs)


@partial(jax.jit, static_argnums=(0,))
def _embed(cfg_items, key, tokens):
    cfg = dict(cfg_items)
    table = _normal(jax.random.fold_in(key, 1),
                    (cfg["vocab_size"], cfg["hidden_size"]), 0.02)
    return table[tokens]


@partial(jax.jit, static_argnums=(0, 4))
def _head(cfg_items, key, xs, rows, control):
    cfg = dict(cfg_items)
    d = cfg["hidden_size"]
    head = _quant(_normal(jax.random.fold_in(jax.random.fold_in(key, 1), 1),
                          (d, cfg["vocab_size"]), d ** -0.5), control)
    x = jnp.take_along_axis(xs, rows[..., None], axis=1)     # B, N, d
    return _mm(_rms(x, cfg.get("rms_norm_eps", 1e-5)), head, control)


def logits(cfg: dict, seed: int, tokens: np.ndarray, rows: np.ndarray,
           controls=(None,)):
    """Logits at ``rows`` (B, N) of each sequence of ``tokens`` (B, T),
    float32, once for each entry of ``controls`` (None is the reference).
    The layers run one at a time over all sequences, one sequence at a
    time inside a layer."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    key = jax.random.PRNGKey(seed)
    layer_keys = jax.random.split(jax.random.fold_in(key, 17),
                                  cfg["num_hidden_layers"])
    out = []
    with jax.default_matmul_precision("highest"):
        x0 = _embed(items, key, jnp.asarray(tokens))
        for control in controls:
            x = x0
            for lk in layer_keys:
                x = _layer_step(items, lk, x, control)
            out.append(np.asarray(_head(items, key, x, jnp.asarray(rows),
                                        control)))
            del x
    return out
