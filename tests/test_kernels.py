"""Per-kernel validation: shape/dtype sweeps + hypothesis, asserting
allclose against the pure-jnp oracles in each kernel's ref.py
(interpret=True executes the Pallas body on CPU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis "
    "(pip install -r requirements-test.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba_scan.kernel import selective_scan_pallas
from repro.kernels.mamba_scan.ops import (selective_scan_chunked,
                                          selective_scan_step)
from repro.kernels.mamba_scan.ref import selective_scan_ref
from repro.kernels.rwkv6.kernel import wkv6_pallas
from repro.kernels.rwkv6.ops import wkv6_chunked, wkv6_step
from repro.kernels.rwkv6.ref import wkv6_ref

RNG = jax.random.PRNGKey(0)


def rand(i, shape, dtype=jnp.float32, lo=-1.0, hi=1.0):
    x = jax.random.uniform(jax.random.fold_in(RNG, i), shape,
                           jnp.float32, lo, hi)
    return x.astype(dtype)


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=5e-2, atol=5e-2)}


# ---------------------------------------------------------------- flash
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,hd,causal,window", [
    (1, 64, 2, 64, True, 0),
    (2, 100, 3, 32, True, 16),
    (1, 128, 2, 128, False, 0),
    (1, 257, 1, 64, True, 64),
    (2, 48, 4, 16, True, 0),
])
def test_flash_attention(b, s, h, hd, causal, window, dtype):
    q = rand(1, (b, s, h, hd), dtype)
    k = rand(2, (b, s, h, hd), dtype)
    v = rand(3, (b, s, h, hd), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


def test_flash_attention_softcap():
    q, k, v = (rand(i, (1, 96, 2, 32)) for i in (1, 2, 3))
    out = flash_attention_pallas(q, k, v, causal=True, softcap=30.0,
                                 block_q=32, block_k=32, interpret=True)
    ref = attention_ref(q, k, v, causal=True, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(s=st.integers(4, 96), h=st.integers(1, 3),
       hd=st.sampled_from([8, 16, 32]), causal=st.booleans(),
       bq=st.sampled_from([16, 32, 64]))
def test_flash_attention_property(s, h, hd, causal, bq):
    q, k, v = (rand(i + s, (1, s, h, hd)) for i in (1, 2, 3))
    out = flash_attention_pallas(q, k, v, causal=causal, block_q=bq,
                                 block_k=bq, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------- wkv6
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,H,hd,chunk", [
    (2, 40, 2, 16, 16), (1, 100, 3, 32, 32), (2, 64, 1, 64, 64),
    (1, 200, 4, 64, 128),     # chip layout: hd=64, default chunk, padded
])
def test_wkv6_kernel(b, s, H, hd, chunk, dtype):
    r, k, v = (rand(i, (b, H, s, hd), dtype) for i in (1, 2, 3))
    w = (jax.nn.sigmoid(rand(4, (b, H, s, hd))) * 0.5 + 0.45).astype(dtype)
    u = rand(5, (H, hd), dtype)
    s0 = rand(6, (b, H, hd, hd))
    y1, S1 = wkv6_pallas(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    y2, S2 = wkv6_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), **TOL[dtype])
    np.testing.assert_allclose(np.asarray(S1), np.asarray(S2),
                               rtol=1e-3, atol=1e-3)


def test_wkv6_chunked_matches_ref():
    """The CPU/dry-run chunked-remat twin is also oracle-exact, including
    non-multiple-of-chunk lengths (decay padded with ONES)."""
    b, s, H, hd = 2, 70, 2, 16
    r, k, v = (rand(i, (b, H, s, hd)) for i in (1, 2, 3))
    w = jax.nn.sigmoid(rand(4, (b, H, s, hd))) * 0.5 + 0.45
    u, s0 = rand(5, (H, hd)), rand(6, (b, H, hd, hd))
    y1, S1 = wkv6_chunked(r, k, v, w, u, s0, chunk=32)
    y2, S2 = wkv6_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(S1), np.asarray(S2),
                               rtol=2e-5, atol=2e-5)


def test_wkv6_step_matches_scan():
    """Single-token decode step == one step of the parallel form."""
    b, H, hd = 2, 2, 16
    r, k, v = (rand(i, (b, H, 1, hd)) for i in (1, 2, 3))
    w = jax.nn.sigmoid(rand(4, (b, H, 1, hd))) * 0.5 + 0.45
    u, s0 = rand(5, (H, hd)), rand(6, (b, H, hd, hd))
    y1, S1 = wkv6_step(r[:, :, 0], k[:, :, 0], v[:, :, 0], w[:, :, 0], u, s0)
    y2, S2 = wkv6_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2[:, :, 0]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(S1), np.asarray(S2),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------- mamba
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,di,N,chunk,bd", [
    (2, 40, 24, 8, 16, 16), (1, 100, 64, 16, 32, 32),
    (2, 33, 48, 4, 16, 48),
    (1, 150, 256, 16, 128, 128),   # chip layout: lane-aligned blocks, N=16
])
def test_mamba_kernel(b, s, di, N, chunk, bd, dtype):
    x = rand(11, (b, s, di), dtype)
    dt = (jax.nn.softplus(rand(12, (b, s, di))) * 0.1).astype(dtype)
    A = -jnp.exp(rand(13, (di, N), lo=0, hi=1))
    B, C = rand(14, (b, s, N), dtype), rand(15, (b, s, N), dtype)
    D, h0 = rand(16, (di,)), rand(17, (b, di, N))
    y1, h1 = selective_scan_pallas(x, dt, A, B, C, D, h0, chunk=chunk,
                                   block_d=bd, interpret=True)
    y2, h2 = selective_scan_ref(x, dt, A, B, C, D, h0)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), **TOL[dtype])
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=1e-3, atol=1e-3)


def test_mamba_chunked_and_step():
    b, s, di, N = 1, 37, 16, 8
    x = rand(11, (b, s, di))
    dt = jax.nn.softplus(rand(12, (b, s, di))) * 0.1
    A = -jnp.exp(rand(13, (di, N), lo=0, hi=1))
    B, C = rand(14, (b, s, N)), rand(15, (b, s, N))
    D, h0 = rand(16, (di,)), rand(17, (b, di, N))
    y1, h1 = selective_scan_chunked(x, dt, A, B, C, D, h0, chunk=16)
    y2, h2 = selective_scan_ref(x, dt, A, B, C, D, h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=2e-5, atol=2e-5)
    ys, hs = selective_scan_step(x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0],
                                 D, h0)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(y2[:, 0]),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=8, deadline=None)
@given(s=st.integers(3, 70), di=st.sampled_from([8, 24]),
       N=st.sampled_from([4, 8]), chunk=st.sampled_from([8, 16]))
def test_mamba_property(s, di, N, chunk):
    x = rand(s, (1, s, di))
    dt = jax.nn.softplus(rand(s + 1, (1, s, di))) * 0.2
    A = -jnp.exp(rand(s + 2, (di, N), lo=0, hi=1))
    B, C = rand(s + 3, (1, s, N)), rand(s + 4, (1, s, N))
    D, h0 = rand(s + 5, (di,)), rand(s + 6, (1, di, N))
    y1, h1 = selective_scan_pallas(x, dt, A, B, C, D, h0, chunk=chunk,
                                   block_d=di, interpret=True)
    y2, h2 = selective_scan_ref(x, dt, A, B, C, D, h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=3e-5, atol=3e-5)
