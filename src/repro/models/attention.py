"""Attention: GQA / sliding-window / local-global / MLA, train+prefill+decode.

Design notes (see DESIGN.md §5):
  * train/prefill use a blockwise online-softmax ("flash") path written in
    pure jnp with lax.scan over KV blocks — this keeps compile-time memory
    linear in seq (no (s,s) score tensor) so the 32k dry-run cells fit.
    On TPU the Pallas kernel in repro.kernels.flash_attention is selected
    by ops.py; the jnp path doubles as its oracle-efficient twin.
  * static sliding windows (Mixtral/Danube) use a q-block × kv-slice path
    whose FLOPs are O(seq·window) instead of O(seq²).
  * decode attends over a KV cache whose seq dim is sharded over `model`
    (flash-decoding layout); softmax reductions over the sharded axis lower
    to small all-reduces under GSPMD.
  * KV heads are computed replicated.  Train/prefill repeat them to
    n_heads before the core (a free slice under head-sharded TP; see
    DESIGN.md); decode contracts each query-head group against its shared
    KV head, so the cache is read at its own head count and dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map

from repro.models import layers as L

NEG_INF = -1e30


def _softcap(x, cap):
    if isinstance(cap, (int, float)) and cap == 0.0:
        return x
    return jnp.tanh(x / cap) * cap


# ---------------------------------------------------------------------------
# params


def init_attention(rng, cfg, dtype, *, cross=False):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    r = L.split_tree(rng, 4)
    p = {
        "wq": L.dense_init(r[0], (d, nq * hd), dtype),
        "wk": L.dense_init(r[1], (d, nkv * hd), dtype),
        "wv": L.dense_init(r[2], (d, nkv * hd), dtype),
        "wo": L.dense_init(r[3], (nq * hd, d), dtype),
    }
    if cfg.qk_norm and not cross:
        p["q_scale"] = jnp.ones((hd,), dtype)
        p["k_scale"] = jnp.ones((hd,), dtype)
    return p


def project_qkv(x, p, cfg, *, kv_x=None):
    """Returns q (b,s,nq,hd), k/v (b,skv,nkv,hd)."""
    b, s, _ = x.shape
    hd, nq, nkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    kv_x = x if kv_x is None else kv_x
    q = (x @ p["wq"]).reshape(b, s, nq, hd)
    k = (kv_x @ p["wk"]).reshape(b, kv_x.shape[1], nkv, hd)
    v = (kv_x @ p["wv"]).reshape(b, kv_x.shape[1], nkv, hd)
    if "q_scale" in p:
        q = L.head_rmsnorm(q) * p["q_scale"]
        k = L.head_rmsnorm(k) * p["k_scale"]
    return q, k, v


def repeat_kv(k, n_heads):
    nkv = k.shape[2]
    if nkv == n_heads:
        return k
    return jnp.repeat(k, n_heads // nkv, axis=2)


# ---------------------------------------------------------------------------
# blockwise flash attention (pure jnp, scan over KV blocks)


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    q_offset=0, block_kv=1024, mask_value=NEG_INF):
    """q (b,sq,h,hd), k/v (b,skv,h,hd) -> (b,sq,h,hd).

    ``window`` may be a python int (0 = none) or a traced scalar (per-layer
    windows inside a scan — gemma3).  ``q_offset`` is the absolute position
    of q[0] (chunked prefill).
    """
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    scale = 1.0 / np.sqrt(hd)
    qf = (q.astype(jnp.float32) * scale).transpose(0, 2, 1, 3)   # b h sq hd

    nb = -(-skv // block_kv)
    pad = nb * block_kv - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.transpose(0, 2, 1, 3).reshape(b, h, nb, block_kv, hd)
    vb = v.transpose(0, 2, 1, 3).reshape(b, h, nb, block_kv, hd)
    kb = jnp.moveaxis(kb, 2, 0)                                   # nb b h bk hd
    vb = jnp.moveaxis(vb, 2, 0)

    q_pos = q_offset + jnp.arange(sq)

    def body(carry, blk):
        m, l, acc = carry
        kblk, vblk, bidx = blk
        k_pos = bidx * block_kv + jnp.arange(block_kv)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kblk.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        s = _softcap(s, softcap)
        mask = k_pos[None, :] < skv                               # pad mask
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if not (isinstance(window, int) and window == 0):
            w = jnp.asarray(window)
            mask &= jnp.where(w > 0,
                              q_pos[:, None] - k_pos[None, :] < w, True)
        s = jnp.where(mask[None, None], s, mask_value)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kb, vb, jnp.arange(nb)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def sliding_window_attention(q, k, v, *, window, softcap=0.0, block_q=512):
    """O(seq·window) path for a *static* python-int window (all layers SWA:
    Mixtral, Danube3).  Each q block attends a static kv slice of length
    window+block_q ending at the block's last row."""
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    assert isinstance(window, int) and window > 0
    nb = -(-sq // block_q)
    pad_q = nb * block_q - sq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    span = window + block_q
    # pad kv front (history) and back (q padding) so slices are static-size
    kp = jnp.pad(k, ((0, 0), (span, pad_q), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (span, pad_q), (0, 0), (0, 0)))
    scale = 1.0 / np.sqrt(hd)

    def body(_, bidx):
        q_blk = jax.lax.dynamic_slice_in_dim(q, bidx * block_q, block_q, 1)
        start = bidx * block_q + block_q - span + span   # in padded coords
        k_blk = jax.lax.dynamic_slice_in_dim(kp, start, span, 1)
        v_blk = jax.lax.dynamic_slice_in_dim(vp, start, span, 1)
        q_pos = bidx * block_q + jnp.arange(block_q)
        k_pos = bidx * block_q + block_q - span + jnp.arange(span)
        s = jnp.einsum("bqhd,bkhd->bhqk",
                       q_blk.astype(jnp.float32) * scale,
                       k_blk.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        s = _softcap(s, softcap)
        mask = (k_pos[None, :] >= 0) & (k_pos[None, :] < skv)
        mask &= q_pos[:, None] >= k_pos[None, :]
        mask &= q_pos[:, None] - k_pos[None, :] < window
        s = jnp.where(mask[None, None], s, NEG_INF)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
                       v_blk.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        return None, o

    _, blocks = jax.lax.scan(body, None, jnp.arange(nb))
    out = jnp.moveaxis(blocks, 0, 1).reshape(b, nb * block_q, h, hd)
    return out[:, :sq].astype(q.dtype)


def decode_attention(q, k_cache, v_cache, length, *, window=0, softcap=0.0):
    """Single-step decode. q (b,1,nq,hd); caches (b,S,nkv,hd) at their own
    head count and dtype, nkv dividing nq — seq dim may be sharded over
    `model`; GSPMD turns the softmax/contraction reductions into small
    all-reduces.  ``length`` = number of valid cache entries (new token
    already written at length-1).

    Each group of g = nq // nkv query heads contracts against its shared
    KV head, so the cache is read once, as it is stored.  Scores, softmax
    and P·V accumulate in f32; p stays f32."""
    b, _, nq, hd = q.shape
    S, nkv = k_cache.shape[1], k_cache.shape[2]
    qg = q.reshape(b, 1, nkv, nq // nkv, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache,
                   preferred_element_type=jnp.float32) * (1.0 / np.sqrt(hd))
    s = _softcap(s, softcap)
    pos = jnp.arange(S)
    mask = pos[None, :] < length
    if not (isinstance(window, int) and window == 0):
        w = jnp.asarray(window)
        mask &= jnp.where(w > 0, pos[None, :] >= length - w, True)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, nq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3): low-rank latent KV, absorbed decode


def init_mla(rng, cfg, dtype):
    m, d, nq = cfg.mla, cfg.d_model, cfg.n_heads
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    r = L.split_tree(rng, 7)
    return {
        "wq_a": L.dense_init(r[0], (d, m.q_lora_rank), dtype),
        "q_norm": jnp.ones((m.q_lora_rank,), dtype),
        "wq_b": L.dense_init(r[1], (m.q_lora_rank, nq * qk_hd), dtype),
        "wkv_a": L.dense_init(r[2], (d, m.kv_lora_rank + m.qk_rope_head_dim),
                              dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "wk_b": L.dense_init(r[3], (m.kv_lora_rank, nq * m.qk_nope_head_dim),
                             dtype),
        "wv_b": L.dense_init(r[4], (m.kv_lora_rank, nq * m.v_head_dim), dtype),
        "wo": L.dense_init(r[5], (nq * m.v_head_dim, d), dtype),
    }


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def mla_latents(x, p, cfg, positions):
    """Compute the cached quantities: c_kv (b,s,r_kv) and k_rope (b,s,1,hd_r)."""
    m = cfg.mla
    kv = x @ p["wkv_a"]
    c_kv, k_rope = kv[..., :m.kv_lora_rank], kv[..., m.kv_lora_rank:]
    c_kv = _rms(c_kv, p["kv_norm"])
    k_rope = L.apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)
    return c_kv, k_rope[:, :, 0, :]


def mla_queries(x, p, cfg, positions):
    m, nq = cfg.mla, cfg.n_heads
    b, s, _ = x.shape
    q = _rms(x @ p["wq_a"], p["q_norm"]) @ p["wq_b"]
    q = q.reshape(b, s, nq, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = (q[..., :m.qk_nope_head_dim],
                      q[..., m.qk_nope_head_dim:])
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_prefill(x, p, cfg, positions):
    """Naive (expanded) MLA for train/prefill; returns out, (c_kv, k_rope)."""
    m, nq = cfg.mla, cfg.n_heads
    b, s, _ = x.shape
    c_kv, k_rope = mla_latents(x, p, cfg, positions)
    q_nope, q_rope = mla_queries(x, p, cfg, positions)
    k_nope = (c_kv @ p["wk_b"]).reshape(b, s, nq, m.qk_nope_head_dim)
    v = (c_kv @ p["wv_b"]).reshape(b, s, nq, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, s, nq, m.qk_rope_head_dim))], axis=-1)
    # pad v to qk head dim so the flash core sees one head dim
    o = flash_attention(q, k, jnp.pad(v, ((0, 0), (0, 0), (0, 0),
                                          (0, q.shape[-1] - v.shape[-1]))),
                        causal=True)
    o = o[..., :m.v_head_dim].reshape(b, s, nq * m.v_head_dim)
    return o @ p["wo"], (c_kv, k_rope)


def mla_decode(x, p, cfg, c_kv_cache, k_rope_cache, length, positions):
    """Absorbed-matmul decode: scores via q_nope·W_kbᵀ against the latent
    cache (never re-expanding per-position K/V).  x (b,1,d)."""
    m, nq = cfg.mla, cfg.n_heads
    b = x.shape[0]
    S = c_kv_cache.shape[1]
    q_nope, q_rope = mla_queries(x, p, cfg, positions)       # (b,1,h,·)
    wk_b = p["wk_b"].reshape(m.kv_lora_rank, nq, m.qk_nope_head_dim)
    q_abs = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(jnp.float32),
                       wk_b.astype(jnp.float32))             # (b,1,h,r_kv)
    s = jnp.einsum("bqhr,bkr->bhqk", q_abs,
                   c_kv_cache.astype(jnp.float32))
    s += jnp.einsum("bqhd,bkd->bhqk", q_rope.astype(jnp.float32),
                    k_rope_cache.astype(jnp.float32))
    s *= 1.0 / np.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    mask = jnp.arange(S)[None, :] < length
    s = jnp.where(mask[None, None], s, NEG_INF)
    pw = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhqk,bkr->bqhr", pw,
                       c_kv_cache.astype(jnp.float32))       # (b,1,h,r_kv)
    wv_b = p["wv_b"].reshape(m.kv_lora_rank, nq, m.v_head_dim)
    o = jnp.einsum("bqhr,rhd->bqhd", o_lat, wv_b.astype(jnp.float32))
    o = o.reshape(b, 1, nq * m.v_head_dim).astype(x.dtype)
    return o @ p["wo"]


# ---------------------------------------------------------------------------
# SP (flash-decoding) shard_map paths — EXPERIMENTS.md §Perf decode iters.
# The KV cache sequence dim stays sharded over dist.kv_seq; each shard
# computes a partial softmax over its slice and the shards combine with
# the log-sum-exp trick (pmax + two psums of (b,h,1[,hd]) — bytes moved
# per layer drop from O(cache) to O(heads·head_dim)).

MASK_VALUE = -1e30   # finite: an all-masked shard yields corr=0, not NaN


def _lse_combine(s, v_l, axes, out_dtype):
    """s (b,h,1,S_l) masked scores; v_l (b,S_l,h,hd) local values."""
    m_l = jnp.max(s, axis=-1)                               # (b,h,1)
    p = jnp.exp(s - m_l[..., None])
    l_l = jnp.sum(p, axis=-1)
    o_l = jnp.einsum("bhqk,bkhd->bhqd", p, v_l.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    m_g = jax.lax.pmax(m_l, axes)
    corr = jnp.exp(m_l - m_g)
    l_g = jax.lax.psum(l_l * corr, axes)
    o_g = jax.lax.psum(o_l * corr[..., None], axes)
    o = o_g / jnp.maximum(l_g[..., None], 1e-30)
    return jnp.moveaxis(o, 1, 2).astype(out_dtype)          # (b,1,h,hd)


def decode_attention_sp(q, k_cache, v_cache, length, dist, *, window=0,
                        softcap=0.0, n_heads=None):
    """Sequence-parallel single-step decode.  q (b,1,nq,hd); caches
    (b,S,nkv,hd) with S sharded over dist.kv_seq.  GQA repeat happens on
    the LOCAL shard.  ``length`` = #valid entries (ring caches pass the
    clamped value)."""
    mesh = dist.mesh
    kv_axes = dist.kv_seq
    dp = dist.batch_axes()
    n_heads = n_heads or q.shape[2]
    S = k_cache.shape[1]
    n_shards = 1
    for a in kv_axes:
        n_shards *= mesh.shape[a]
    S_l = S // n_shards
    scale = 1.0 / np.sqrt(q.shape[-1])

    def local_fn(q_l, k_l, v_l, length):
        idx = jnp.int32(0)
        for a in kv_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        pos0 = idx * S_l
        # grouped-GQA: contract q-head groups against the SHARED kv head
        # directly — never materializes the g-times-repeated (and
        # f32-upcast) cache (perf iter: internvl2 decode)
        b, _, nq, hd = q_l.shape
        kvh = k_l.shape[2]
        g = nq // kvh
        # bf16 operands + f32 accumulation: MXU-native, avoids the
        # materialized f32 cache copy the upcast version produced
        qg = (q_l.astype(jnp.float32) * scale).astype(k_l.dtype)
        qg = qg.reshape(b, 1, kvh, g, hd)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_l,
                       preferred_element_type=jnp.float32)
        s = _softcap(s, softcap)
        pos = pos0 + jnp.arange(S_l)
        mask = pos[None, :] < length
        if not (isinstance(window, int) and window == 0):
            w = jnp.asarray(window)
            mask = mask & jnp.where(w > 0, pos[None, :] >= length - w,
                                    True)
        s = jnp.where(mask[None, None, None], s, MASK_VALUE)
        m_l = jnp.max(s, axis=-1)                       # (b,kvh,g,1)
        p = jnp.exp(s - m_l[..., None])
        l_l = jnp.sum(p, axis=-1)
        o_l = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v_l.dtype), v_l,
                         preferred_element_type=jnp.float32)
        m_g = jax.lax.pmax(m_l, kv_axes)
        corr = jnp.exp(m_l - m_g)
        l_g = jax.lax.psum(l_l * corr, kv_axes)
        o_g = jax.lax.psum(o_l * corr[..., None], kv_axes)
        o = o_g / jnp.maximum(l_g[..., None], 1e-30)    # (b,kvh,g,1,hd)
        return jnp.moveaxis(o.reshape(b, nq, 1, hd), 1, 2).astype(
            q_l.dtype)

    from jax.sharding import PartitionSpec as P
    kv = dist.kv_axes()
    return shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dp, None, None, None), P(dp, kv, None, None),
                  P(dp, kv, None, None), P()),
        out_specs=P(dp, None, None, None),
        check_vma=False)(q, k_cache, v_cache, length)


def mla_decode_sp(x, p, cfg, c_kv_cache, k_rope_cache, length, positions,
                  dist):
    """Sequence-parallel absorbed-matmul MLA decode: the latent cache
    (b,S,r_kv) stays sharded on S; scores and the latent attention
    readout combine via LSE."""
    m, nq = cfg.mla, cfg.n_heads
    b = x.shape[0]
    mesh = dist.mesh
    kv_axes = dist.kv_seq
    dp = dist.batch_axes()
    S = c_kv_cache.shape[1]
    n_shards = 1
    for a in kv_axes:
        n_shards *= mesh.shape[a]
    S_l = S // n_shards
    scale = 1.0 / np.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)

    q_nope, q_rope = mla_queries(x, p, cfg, positions)       # (b,1,h,·)
    wk_b = p["wk_b"].reshape(m.kv_lora_rank, nq, m.qk_nope_head_dim)
    q_abs = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(jnp.float32),
                       wk_b.astype(jnp.float32))             # (b,1,h,r)

    def local_fn(q_abs_l, q_rope_l, ckv_l, krope_l, length):
        idx = jnp.int32(0)
        for a in kv_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        pos0 = idx * S_l
        s = jnp.einsum("bqhr,bkr->bhqk", q_abs_l,
                       ckv_l.astype(jnp.float32))
        s += jnp.einsum("bqhd,bkd->bhqk", q_rope_l.astype(jnp.float32),
                        krope_l.astype(jnp.float32))
        s *= scale
        pos = pos0 + jnp.arange(S_l)
        s = jnp.where((pos[None, :] < length)[None, None], s, MASK_VALUE)
        # latent-space LSE combine: "values" are the latent cache itself
        m_l = jnp.max(s, axis=-1)
        pw = jnp.exp(s - m_l[..., None])
        l_l = jnp.sum(pw, axis=-1)
        o_l = jnp.einsum("bhqk,bkr->bhqr", pw, ckv_l.astype(jnp.float32))
        m_g = jax.lax.pmax(m_l, kv_axes)
        corr = jnp.exp(m_l - m_g)
        l_g = jax.lax.psum(l_l * corr, kv_axes)
        o_g = jax.lax.psum(o_l * corr[..., None], kv_axes)
        return o_g / jnp.maximum(l_g[..., None], 1e-30)     # (b,h,1,r)

    from jax.sharding import PartitionSpec as P
    kv = dist.kv_axes()
    o_lat = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dp, None, None, None), P(dp, None, None, None),
                  P(dp, kv, None), P(dp, kv, None), P()),
        out_specs=P(dp, None, None, None),
        check_vma=False)(q_abs, q_rope, c_kv_cache, k_rope_cache, length)
    o_lat = jnp.moveaxis(o_lat, 1, 2)                        # (b,1,h,r)
    wv_b = p["wv_b"].reshape(m.kv_lora_rank, nq, m.v_head_dim)
    o = jnp.einsum("bqhr,rhd->bqhd", o_lat, wv_b.astype(jnp.float32))
    o = o.reshape(b, 1, nq * m.v_head_dim).astype(x.dtype)
    return o @ p["wo"]
