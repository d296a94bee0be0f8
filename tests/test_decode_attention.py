"""The grouped decode core against the repeat-and-upcast core it replaced.

``decode_attention`` contracts each group of nq // nkv query heads against
its shared KV head, with the cache at its own dtype.  The oracle below is
the earlier formulation: repeat K/V to nq heads, cast them to float32,
then score, mask, softmax and contract.  Both must agree for every head
grouping, window kind, softcap, fill and cache dtype the models use.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention as A

B, S, HD = 2, 16, 8


def _oracle(q, k_cache, v_cache, length, *, window=0, softcap=0.0):
    b, _, nq, hd = q.shape
    k = A.repeat_kv(k_cache, nq).astype(jnp.float32)
    v = A.repeat_kv(v_cache, nq).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk",
                   q.astype(jnp.float32) * (1.0 / np.sqrt(hd)), k,
                   preferred_element_type=jnp.float32)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    pos = jnp.arange(k.shape[1])
    mask = pos[None, :] < length
    if not (isinstance(window, int) and window == 0):
        w = jnp.asarray(window)
        mask &= jnp.where(w > 0, pos[None, :] >= length - w, True)
    s = jnp.where(mask[None, None], s, A.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("length", [1, S // 2 + 1, S],
                         ids=["first", "mid", "full"])
@pytest.mark.parametrize("softcap", [0.0, 30.0], ids=["nocap", "cap30"])
@pytest.mark.parametrize("window", ["none", "static", "traced"])
@pytest.mark.parametrize("nq,nkv", [(4, 4), (4, 2), (32, 8), (8, 1)],
                         ids=["mha", "g2", "g4", "mqa"])
def test_grouped_decode_matches_repeated_upcast(nq, nkv, window, softcap,
                                                length, dtype):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(nq * 7 + nkv), 3)
    # scale so that scores reach the softcap and the softmax is not flat
    q = (3.0 * jax.random.normal(kq, (B, 1, nq, HD))).astype(dtype)
    k_cache = (3.0 * jax.random.normal(kk, (B, S, nkv, HD))).astype(dtype)
    v_cache = jax.random.normal(kv, (B, S, nkv, HD)).astype(dtype)
    n_valid = jnp.int32(length)
    if window == "traced":
        # a per-layer window arrives as a scalar traced inside the scan
        run = jax.jit(lambda f, w: f(q, k_cache, v_cache, n_valid,
                                     window=w, softcap=softcap),
                      static_argnums=0)
        got = run(A.decode_attention, jnp.int32(5))
        want = run(_oracle, jnp.int32(5))
    else:
        w = {"none": 0, "static": 5}[window]
        got = A.decode_attention(q, k_cache, v_cache, n_valid, window=w,
                                 softcap=softcap)
        want = _oracle(q, k_cache, v_cache, n_valid, window=w,
                       softcap=softcap)
    assert got.shape == (B, 1, nq, HD) and got.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
