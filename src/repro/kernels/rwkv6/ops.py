"""Dispatching wrapper for the WKV6 recurrence.

* TPU: Pallas kernel (kernel.py) with per-head state tiles resident in VMEM.
* CPU/dry-run: chunked lax.scan with per-chunk rematerialization — the
  memory-safe twin of the kernel (backward stores only chunk-boundary
  states, never per-step states).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.rwkv6.ref import wkv6_ref


def _pad_time(t, chunk, value=0.0):
    """Pad the time axis (2 of (b, H, s, K)) to a multiple of ``chunk``."""
    s = t.shape[2]
    pad = (-s) % chunk
    if pad:
        t = jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0)),
                    constant_values=value)
    return t, s


def wkv6_chunked(r, k, v, w, u, state, *, chunk=128):
    """Same contract as wkv6_ref; seq processed in remat'ed chunks so the
    backward pass is O(s/chunk) state storage.

    Padding: k/v/r pad with zeros (no contribution) but the decay ``w``
    pads with ONES — a padded step must leave the state untouched
    (S = 1·S + 0), not erase it (S = 0·S + 0)."""
    (r, s0), (k, _), (v, _) = (_pad_time(t, chunk) for t in (r, k, v))
    w, _ = _pad_time(w, chunk, value=1.0)
    b, H, s, K = r.shape
    nb = s // chunk

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(S, ts):
        y, S = wkv6_ref(ts[0], ts[1], ts[2], ts[3], u, S)
        return S, y

    xs = tuple(jnp.moveaxis(t.reshape(b, H, nb, chunk, -1), 2, 0)
               for t in (r, k, v, w))
    S, ys = jax.lax.scan(body, state.astype(jnp.float32), xs)
    y = jnp.moveaxis(ys, 0, 2).reshape(b, H, s, -1)[:, :, :s0]
    return y.astype(r.dtype), S


def wkv6(r, k, v, w, u, state, *, chunk=128, use_pallas=None):
    """r/k/v/w (b, H, s, K), heads-major; u (H, K); state (b, H, K, V).
    Returns (y (b, H, s, V), final state f32)."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        from repro.kernels.rwkv6.kernel import wkv6_pallas
        return wkv6_pallas(r, k, v, w, u, state)
    return wkv6_chunked(r, k, v, w, u, state, chunk=chunk)


def wkv6_step(r1, k1, v1, w1, u, state):
    """Single-token decode step. r1... (b,H,K); state (b,H,K,V)."""
    rf, kf, vf, wf = (t.astype(jnp.float32) for t in (r1, k1, v1, w1))
    uf = u.astype(jnp.float32)
    outer = kf[..., :, None] * vf[..., None, :]
    y = jnp.einsum("bhk,bhkv->bhv", rf,
                   state + uf[None, :, :, None] * outer)
    state = wf[..., :, None] * state + outer
    return y.astype(r1.dtype), state
