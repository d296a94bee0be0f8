"""RWKV6 "Finch" block: data-dependent-decay time mix + channel mix.

Faithful structure: ddlerp token-shift (5-way LoRA mix), data-dependent
decay via LoRA, per-head WKV recurrence (kernels.rwkv6), grouped head norm,
squared-ReLU channel mix.  Heads are d_model/head_dim wide; TP shards the
head dim of the time-mix projections over `model` (recurrence is per-head
local).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.rwkv6 import ops as wkv_ops
from repro.models import layers as L

MIX_KEYS = ("r", "k", "v", "w", "g")


def init_time_mix(rng, cfg, dtype):
    d = cfg.d_model
    rw = cfg.rwkv
    r = L.split_tree(rng, 12)
    p = {
        "mu_x": jnp.zeros((d,), dtype),
        "mu": jnp.zeros((5, d), dtype),
        "mix_w1": L.dense_init(r[0], (d, 5 * rw.mix_lora), dtype),
        "mix_w2": L.dense_init(r[1], (5, rw.mix_lora, d), dtype,
                               fan_in=rw.mix_lora),
        "w0": jnp.full((d,), -6.0, dtype),          # decay bias (slow decay)
        "decay_w1": L.dense_init(r[2], (d, rw.decay_lora), dtype),
        "decay_w2": L.dense_init(r[3], (rw.decay_lora, d), dtype,
                                 fan_in=rw.decay_lora),
        "u": (jax.random.normal(r[4], (d,), jnp.float32) * 0.1).astype(dtype),
        "wr": L.dense_init(r[5], (d, d), dtype),
        "wk": L.dense_init(r[6], (d, d), dtype),
        "wv": L.dense_init(r[7], (d, d), dtype),
        "wg": L.dense_init(r[8], (d, d), dtype),
        "wo": L.dense_init(r[9], (d, d), dtype),
        "ln_scale": jnp.ones((d,), dtype),
    }
    return p


def init_channel_mix(rng, cfg, dtype):
    d, ff = cfg.d_model, cfg.d_ff
    r = L.split_tree(rng, 3)
    return {
        "mu_k": jnp.zeros((d,), dtype),
        "mu_r": jnp.zeros((d,), dtype),
        "wk": L.dense_init(r[0], (d, ff), dtype),
        "wv": L.dense_init(r[1], (ff, d), dtype),
        "wr": L.dense_init(r[2], (d, d), dtype),
    }


def _token_shift(x, last):
    """shift(x)_t = x_{t-1}; position 0 takes ``last`` (decode carry)."""
    shifted = jnp.concatenate([last[:, None, :], x[:, :-1, :]], axis=1)
    return shifted - x


def time_mix(x, p, cfg, state, last_x):
    """x (b,s,d); state (b,H,K,K) wkv state; last_x (b,d) shift carry.
    Returns y, (new_state, new_last_x)."""
    b, s, d = x.shape
    hd = cfg.rwkv.head_dim
    H = d // hd
    xx = _token_shift(x, last_x)
    xxx = x + xx * p["mu_x"]
    mix = jnp.tanh(xxx @ p["mix_w1"]).reshape(b, s, 5, -1)
    deltas = jnp.einsum("bsfl,fld->bsfd", mix, p["mix_w2"])
    mixed = {key: x + xx * (p["mu"][i] + deltas[:, :, i])
             for i, key in enumerate(MIX_KEYS)}

    # the head-wise part runs heads-major, (b, H, s, hd): the projections
    # write that layout and ``wo`` reads it back, so no transpose stands
    # between them and the WKV kernel
    heads = lambda t, wt: jnp.einsum("bsd,dhk->bhsk", t,
                                     wt.reshape(wt.shape[0], H, hd))
    r, k, v = (heads(mixed[n], p["w" + n]) for n in "rkv")
    g = jax.nn.silu(heads(mixed["g"], p["wg"]))

    dw = heads(jnp.tanh(mixed["w"] @ p["decay_w1"]), p["decay_w2"])
    w = jnp.exp(-jnp.exp((p["w0"].astype(jnp.float32).reshape(H, 1, hd)
                          + dw.astype(jnp.float32))))

    u = p["u"].reshape(H, hd)
    if s == 1:
        y, new_state = wkv_ops.wkv6_step(r[:, :, 0], k[:, :, 0], v[:, :, 0],
                                         w[:, :, 0], u, state)
        y = y[:, :, None]
    else:
        y, new_state = wkv_ops.wkv6(r, k, v, w, u, state)
    # per-head group norm
    yf = y.astype(jnp.float32)
    mu = yf.mean(-1, keepdims=True)
    var = yf.var(-1, keepdims=True)
    yf = (yf - mu) * jax.lax.rsqrt(var + 64e-5)
    y = (yf * p["ln_scale"].astype(jnp.float32).reshape(H, 1, hd)
         ).astype(x.dtype)
    out = jnp.einsum("bhsk,hkd->bsd", y * g, p["wo"].reshape(H, hd, d))
    return out, (new_state, x[:, -1, :])


def channel_mix(x, p, last_x):
    xx = _token_shift(x, last_x)
    xk = x + xx * p["mu_k"]
    xr = x + xx * p["mu_r"]
    k = jnp.square(jax.nn.relu(xk @ p["wk"]))
    return jax.nn.sigmoid(xr @ p["wr"]) * (k @ p["wv"]), x[:, -1, :]


def init_state(cfg, batch, dtype=jnp.float32):
    d, hd = cfg.d_model, cfg.rwkv.head_dim
    H = d // hd
    return {
        "wkv": jnp.zeros((batch, H, hd, hd), jnp.float32),
        "tm_x": jnp.zeros((batch, d), dtype),
        "cm_x": jnp.zeros((batch, d), dtype),
    }
