"""Pure-jnp oracle for the RWKV6 (Finch) WKV recurrence.

    S_t = diag(w_t)·S_{t-1} + k_tᵀ⊗v_t
    y_t = r_t·(S_{t-1} + diag(u)·k_tᵀ⊗v_t)

Shapes: r,k,v,w (b, H, s, K[=V]), heads-major; u (H, K); state
(b, H, K, V).
w is the *decay* already mapped to (0,1) = exp(-exp(·)).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def wkv6_ref(r, k, v, w, u, state):
    rf, kf, vf, wf = (t.astype(jnp.float32) for t in (r, k, v, w))
    uf = u.astype(jnp.float32)

    def step(S, ts):
        rt, kt, vt, wt = ts                      # (b,H,K) / (b,H,V)
        outer = kt[..., :, None] * vt[..., None, :]          # (b,H,K,V)
        y = jnp.einsum("bhk,bhkv->bhv", rt,
                       S + uf[None, :, :, None] * outer)
        S = wt[..., :, None] * S + outer
        return S, y

    xs = tuple(jnp.moveaxis(t, 2, 0) for t in (rf, kf, vf, wf))
    S, ys = jax.lax.scan(step, state.astype(jnp.float32), xs)
    y = jnp.moveaxis(ys, 0, 2)                   # (b,H,s,V)
    return y.astype(r.dtype), S
