"""Decoder-only LM assembly (dense / MoE / VLM-prefix / MLA), scanned.

One ``lax.scan`` over stacked layer params keeps HLO size O(1) in depth.
Layer heterogeneity that only changes *numbers* (gemma3 local/global window
+ rope theta) rides along as per-layer scalar xs; heterogeneity that changes
*structure* (Jamba) lives in hybrid.py instead.

The KV cache is one stacked array per leaf, layout (L, b, S, h, hd) sharded
(None, dp, `model`, None, None) — the flash-decoding layout (DESIGN.md §5).
Prefill and decode carry it through the layer scan: each layer writes only
its new rows into it, in place, and decode attention reads the layer's
slice of the carry; training has no cache.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import PartitionSpec as P

from repro.models import attention as A
from repro.models import common as C
from repro.models import layers as L
from repro.models import moe as M
from repro.distribution.context import MeshContext, NULL_CTX


def layer_scalars(cfg):
    """Per-layer (window, rope_theta) arrays for the scan."""
    Ln = cfg.n_layers
    win = np.zeros((Ln,), np.int32)
    theta = np.full((Ln,), cfg.rope_theta, np.float32)
    for l in range(Ln):
        if cfg.local_global_period:
            if cfg.layer_is_global(l):
                win[l] = 0
                theta[l] = cfg.global_rope_theta or cfg.rope_theta
            else:
                win[l] = cfg.local_window
        elif cfg.sliding_window:
            win[l] = cfg.sliding_window
    return jnp.asarray(win), jnp.asarray(theta)


def _as_stored(update, shape, device):
    """Give a cache write's ``update`` the layout that the cache of
    ``shape`` is stored in on ``device`` between programs (the layout a
    jitted program's arguments and results take).  A K/V row comes out of
    a matrix product in the product's layout; left free, the compiler may
    instead give the loop-carried cache the row's layout, and copy the
    whole cache into and out of the layer loop on every step.  A TPU
    stores a cache whose head size is not a multiple of 128 with its
    sequence dim minor.  ``device`` None asks no layout."""
    if device is None:
        return update
    layout = device.client.get_default_layout(update.dtype, tuple(shape),
                                               device)
    order = tuple(Layout.from_pjrt_layout(layout).major_to_minor)
    return with_layout_constraint(update, Layout(order))


class DecoderLM:
    """cfg + mesh-context bound, pure-functional methods."""

    def __init__(self, cfg, dist: Optional[MeshContext] = None):
        self.cfg = cfg
        self.dist = dist or NULL_CTX
        self.dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        tp = self.dist.tp_size
        self.shard_heads = (cfg.mla is None and cfg.n_heads % tp == 0
                            and (cfg.n_heads * cfg.resolved_head_dim) % tp == 0)
        # uniform static window -> O(s·w) attention path
        self.static_window = (cfg.sliding_window if cfg.sliding_window and
                              not cfg.local_global_period else 0)
        self.router_mode = ("sigmoid" if cfg.moe and cfg.moe.n_experts >= 64
                            else "softmax_topk")
        if cfg.moe and self.dist.active:
            self.moe_ep = cfg.moe.n_experts % tp == 0 and \
                cfg.moe.n_experts >= tp
        else:
            self.moe_ep = False
        # perf knobs (set by launch.specs from --overrides; defaults are
        # the paper-faithful baseline)
        self.sp_decode = False        # shard_map flash-decoding
        self.window_cache = False     # ring-buffer KV cache for SWA
        self.moe_full_ep = False      # experts over (data x model)
        self.no_fsdp_experts = False  # serving: replicate experts on data
        self.remat_policy = None      # None | "dots" (checkpoint policy)
        # off a mesh, the device that holds the cache between steps, whose
        # stored layout the cache writes take (serving.ModelServer sets it
        # from the weights' device); None asks no layout of them
        self.cache_device = None

    def full_ep_available(self):
        cfg, dist = self.cfg, self.dist
        if cfg.moe is None or not dist.active:
            return False
        n = dist.mesh.shape.get("data", 1) * dist.mesh.shape.get(
            "model", 1)
        return cfg.moe.n_experts % n == 0 and cfg.moe.n_experts >= n

    # ------------------------------------------------------------------ init

    def _init_layer(self, rng):
        cfg, dt = self.cfg, self.dtype
        r = L.split_tree(rng, 4)
        p = {"ln1": L.init_norm(cfg, dt), "ln2": L.init_norm(cfg, dt)}
        if cfg.mla is not None:
            p["attn"] = A.init_mla(r[0], cfg, dt)
        else:
            p["attn"] = A.init_attention(r[1], cfg, dt)
        if cfg.moe is not None and cfg.layer_is_moe(0):
            p["ffn"] = M.init_moe(r[2], cfg, dt)
        else:
            p["ffn"] = L.init_mlp(r[3], cfg.d_model, cfg.d_ff, cfg.act, dt)
        return p

    def init(self, rng):
        cfg = self.cfg
        rngs = jax.random.split(jax.random.fold_in(rng, 17), cfg.n_layers)
        params = {
            "embed": C.init_embedding(jax.random.fold_in(rng, 1), cfg,
                                      self.dtype),
            "layers": jax.vmap(self._init_layer)(rngs),
            "final_norm": L.init_norm(cfg, self.dtype),
        }
        if cfg.mtp_depth:
            r = jax.random.fold_in(rng, 23)
            params["mtp"] = {
                "proj": L.dense_init(r, (2 * cfg.d_model, cfg.d_model),
                                     self.dtype),
                "layer": self._init_layer(jax.random.fold_in(r, 1)),
                "norm": L.init_norm(cfg, self.dtype),
            }
        return params

    # ------------------------------------------------------- shardings (MoE)

    def moe_param_specs(self, stacked: bool):
        """Single source of truth for expert-weight sharding; used for both
        shard_map in_specs (unstacked) and global param shardings (stacked,
        leading layer dim)."""
        pre = (None,) if stacked else ()
        if self.moe_full_ep and self.full_ep_available():
            ed = ("data", "model")
            w = {"router": P(*pre, None, None),
                 "gate": P(*pre, ed, None, None),
                 "up": P(*pre, ed, None, None),
                 "down": P(*pre, ed, None, None)}
        elif self.moe_ep:
            w = {"router": P(*pre, None, None),
                 "gate": P(*pre, "model", None, None),
                 "up": P(*pre, "model", None, None),
                 "down": P(*pre, "model", None, None)}
        else:
            w = {"router": P(*pre, None, None),
                 "gate": P(*pre, None, None, "model"),
                 "up": P(*pre, None, None, "model"),
                 "down": P(*pre, None, "model", None)}
        if self.cfg.moe and self.cfg.moe.n_shared_experts:
            w["shared"] = {"gate": P(*pre, None, "model"),
                           "up": P(*pre, None, "model"),
                           "down": P(*pre, "model", None)}
        return w

    def _moe(self, x, mp, mode="train"):
        cfg, dist = self.cfg, self.dist
        if not dist.active:
            return M.apply_moe(x, mp, cfg, router_mode=self.router_mode)
        dp = dist.batch_axes()
        all_axes = tuple(a for a in ("pod", "data", "model")
                         if a in dist.mesh.axis_names)

        if self.moe_full_ep and self.full_ep_available():
            # Full EP (perf iters 3/5): one (or few) experts per chip,
            # weights never move; tokens all-gather over `data`, outputs
            # psum back in bf16 and each rank keeps its batch slice.
            tp_sz = dist.mesh.shape["model"]
            data_sz = dist.mesh.shape.get("data", 1)
            n_local = cfg.moe.n_experts // (tp_sz * data_sz)
            has_data = "data" in dist.mesh.axis_names

            def local_fn(xl, mpl):
                xg = (jax.lax.all_gather(xl, "data", axis=0, tiled=True)
                      if has_data else xl)
                di = (jax.lax.axis_index("data") if has_data
                      else jnp.int32(0))
                e_off = (di * tp_sz
                         + jax.lax.axis_index("model")) * n_local
                y, aux = M.apply_moe(
                    xg, mpl, cfg, router_mode=self.router_mode,
                    e_offset=e_off,
                    combine_axes=tuple(a for a in ("data", "model")
                                       if a in dist.mesh.axis_names),
                    combine_dtype=self.dtype,
                    shared_scale=1.0 / data_sz)
                if has_data:
                    y = jax.lax.dynamic_slice_in_dim(
                        y, di * xl.shape[0], xl.shape[0], 0)
                return y, jax.lax.pmean(aux, all_axes)

            return shard_map(
                local_fn, mesh=dist.mesh,
                in_specs=(P(dp, None, None), self.moe_param_specs(False)),
                out_specs=(P(dp, None, None), P()),
                check_vma=False)(x, mp)

        ep = "model" if self.moe_ep else None
        tp = None if self.moe_ep else "model"

        def local_fn(xl, mpl):
            y, aux = M.apply_moe(xl, mpl, cfg, router_mode=self.router_mode,
                                 ep_axis=ep, tp_axis=tp)
            return y, jax.lax.pmean(aux, all_axes)

        return shard_map(
            local_fn, mesh=dist.mesh,
            in_specs=(P(dp, None, None), self.moe_param_specs(False)),
            out_specs=(P(dp, None, None), P()),
            check_vma=False)(x, mp)

    # -------------------------------------------------------------- layers

    def _attn_specs(self):
        dp = self.dist.batch_axes()
        h = "model" if self.shard_heads else None
        return dp, h

    def _layer_cache(self, cache, layer):
        """One layer's entry of a stacked cache (``layer`` None: ``cache``
        already is one layer's)."""
        if layer is None:
            return cache
        return jax.tree.map(
            lambda c: jax.lax.dynamic_index_in_dim(c, layer, 0, False), cache)

    def _cache_device(self):
        """A device that holds the cache: the mesh's, else
        ``cache_device``."""
        if self.dist.active:
            return self.dist.mesh.devices.flat[0]
        return self.cache_device

    def _write_rows(self, cache, rows, at, layer=None):
        """Write ``rows`` (leaves (b, T, ...)) at sequence position ``at``
        of the cache: of layer ``layer`` of a stacked cache (leaves
        (L, b, S, ...)), or of a one-layer cache (leaves (b, S, ...)) when
        ``layer`` is None.  In place: no other slot is read or moved.

        Where the cache's sequence dim is split over the mesh, each chip
        writes the positions it holds, at a local offset, and leaves the
        others' untouched; a dynamic offset on a split dim would otherwise
        make the partitioner rewrite or gather whole shards."""
        lead = () if layer is None else (layer,)
        dist = self.dist
        kv = dist.kv_axes()
        device = self._cache_device()
        if not dist.active or kv is None:
            def put(c, r):
                index = lead + (0, at) + (0,) * (r.ndim - 2)
                r = r.astype(c.dtype).reshape((1,) * len(lead) + r.shape)
                return jax.lax.dynamic_update_slice(
                    c, _as_stored(r, c.shape, device), index)
            return jax.tree.map(put, cache, rows)

        mesh, axes = dist.mesh, dist.kv_seq
        dp = dist.batch_axes()
        pre = (None,) * len(lead)

        def local(c, r, at, *lead):
            shard = jnp.int32(0)
            for a in axes:
                shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
            S_l, T = c.shape[len(lead) + 1], r.shape[1]
            n = min(T, S_l)
            start = shard * S_l
            off = jnp.clip(at - start, 0, S_l - n)
            src = start + off + jnp.arange(n) - at     # rows' own indices
            own = (src >= 0) & (src < T)
            new = jnp.take(r, jnp.clip(src, 0, T - 1), axis=1)
            new = new.astype(c.dtype)
            index = lead + (0, off) + (0,) * (r.ndim - 2)
            size = (1,) * len(lead) + new.shape
            old = jax.lax.dynamic_slice(c, index, size).reshape(new.shape)
            own = own.reshape((1, n) + (1,) * (r.ndim - 2))
            new = jnp.where(own, new, old).reshape(size)
            return jax.lax.dynamic_update_slice(
                c, _as_stored(new, c.shape, device), index)

        def put(c, r):
            tail = (None,) * (r.ndim - 2)
            return shard_map(
                local, mesh=mesh,
                in_specs=(P(*pre, dp, kv, *tail), P(dp, None, *tail), P())
                + (P(),) * len(lead),
                out_specs=P(*pre, dp, kv, *tail),
                check_vma=False)(c, r, jnp.asarray(at, jnp.int32), *lead)
        return jax.tree.map(put, cache, rows)

    def _attention_full(self, x, ap, win, theta, positions, cache, length,
                        layer=None):
        """Train/prefill attention. ``cache`` None (train), or the cache
        that prefill writes the prompt's K/V into at position 0 (of layer
        ``layer`` when stacked). Returns (out, cache)."""
        cfg, dist = self.cfg, self.dist
        dp, hshard = self._attn_specs()
        if cfg.mla is not None:
            out, (c_kv, k_rope) = A.mla_prefill(x, ap, cfg, positions)
            if cache is not None:
                cache = self._write_rows(
                    cache, {"ckv": c_kv, "krope": k_rope}, 0, layer)
            return out, cache
        q, k, v = A.project_qkv(x, ap, cfg)
        if not cfg.no_rope:
            q = L.apply_rope(q, positions, theta)
            k = L.apply_rope(k, positions, theta)
        if cache is not None:
            cache = self._write_rows(cache, {"k": k, "v": v}, 0, layer)
        k = A.repeat_kv(k, cfg.n_heads)
        v = A.repeat_kv(v, cfg.n_heads)
        q = dist.wsc(q, dp, None, hshard, None)
        k = dist.wsc(k, dp, None, hshard, None)
        v = dist.wsc(v, dp, None, hshard, None)
        if self.static_window:
            o = A.sliding_window_attention(q, k, v, window=self.static_window,
                                           softcap=cfg.attn_logit_softcap)
        else:
            o = A.flash_attention(q, k, v, causal=True, window=win,
                                  softcap=cfg.attn_logit_softcap)
        b, s = x.shape[:2]
        o = o.reshape(b, s, -1)
        out = dist.wsc(o @ ap["wo"], dp, None, None)
        return out, cache

    def _attention_decode(self, x, ap, win, theta, cache, length,
                          layer=None):
        """One decode step's attention: the new token's K/V rows are
        written into ``cache`` (layer ``layer`` of a stacked cache, or a
        one-layer cache when None), then attention reads that layer's
        entry.  Returns (out, cache)."""
        cfg, dist = self.cfg, self.dist
        dp = dist.batch_axes()
        kv = dist.kv_axes()
        positions = jnp.full((x.shape[0], 1), length, jnp.int32)
        if cfg.mla is not None:
            c_kv, k_rope = A.mla_latents(x, ap, cfg, positions)
            cache = self._write_rows(
                cache, {"ckv": c_kv, "krope": k_rope}, length, layer)
            ce = self._layer_cache(cache, layer)
            ckv_c = dist.wsc(ce["ckv"], dp, kv, None)
            krope_c = dist.wsc(ce["krope"], dp, kv, None)
            if self.sp_decode and dist.active:
                out = A.mla_decode_sp(x, ap, cfg, ckv_c, krope_c,
                                      length + 1, positions, dist)
            else:
                out = A.mla_decode(x, ap, cfg, ckv_c, krope_c, length + 1,
                                   positions)
            return out, cache
        q, k, v = A.project_qkv(x, ap, cfg)
        if not cfg.no_rope:
            q = L.apply_rope(q, positions, theta)
            k = L.apply_rope(k, positions, theta)
        S_cache = cache["k"].shape[-3]
        if self.window_cache:
            # ring buffer (perf iter, SWA long-context): slot = pos % W;
            # keys stored pre-rotated, so attention over slots is
            # permutation-safe and no window mask is needed.
            write_at = jnp.mod(length, S_cache)
            n_valid = jnp.minimum(length + 1, S_cache)
            win = 0
        else:
            write_at = length
            n_valid = length + 1
        cache = self._write_rows(cache, {"k": k, "v": v}, write_at, layer)
        ce = self._layer_cache(cache, layer)
        k_c = dist.wsc(ce["k"], dp, kv, None, None)
        v_c = dist.wsc(ce["v"], dp, kv, None, None)
        if self.sp_decode and dist.active:
            o = A.decode_attention_sp(q, k_c, v_c, n_valid, dist,
                                      window=win,
                                      softcap=cfg.attn_logit_softcap,
                                      n_heads=cfg.n_heads)
        else:
            o = A.decode_attention(q, k_c, v_c, n_valid, window=win,
                                   softcap=cfg.attn_logit_softcap)
        out = o.reshape(x.shape[0], 1, -1) @ ap["wo"]
        return dist.wsc(out, dp, None, None), cache

    def _ffn(self, x, fp, mode="train"):
        if self.cfg.moe is not None:
            return self._moe(x, fp, mode)
        return L.apply_mlp(x, fp, self.cfg.act), jnp.float32(0.0)

    def _layer(self, x, lp, win, theta, positions, cache, length, mode,
               layer=None):
        # the scopes name the device's operations by model part (their
        # op_name metadata: ``.../attention/...``, ``.../mlp/...``)
        cfg = self.cfg
        rs = C.residual_scale(cfg)
        with jax.named_scope("attention"):
            h = L.apply_norm(x, lp["ln1"], cfg)
            if mode == "decode":
                attn, cache = self._attention_decode(
                    h, lp["attn"], win, theta, cache, length, layer)
            else:
                attn, cache = self._attention_full(
                    h, lp["attn"], win, theta, positions, cache, length,
                    layer)
        x = x + attn * rs
        with jax.named_scope("mlp"):
            h = L.apply_norm(x, lp["ln2"], cfg)
            ffn, aux = self._ffn(h, lp["ffn"], mode)
        x = x + ffn * rs
        return x, cache, aux

    # ------------------------------------------------------------- forwards

    def _run_layers(self, x, params, positions, cache, length, mode,
                    remat=False):
        """The layer scan.  Prefill and decode carry the stacked cache
        (held at ``cache_specs`` on entry and exit, so that prefill's
        output and decode's input and output share one sharding); each
        layer writes its new rows into it in place.  Training passes and
        carries none."""
        win, theta = layer_scalars(self.cfg)
        if cache is not None:
            cache = self._pin_cache(cache)

        def body(carry, xs):
            h, c = carry
            lp, w, t, layer = xs
            h, c, aux = self._layer(h, lp, w, t, positions, c, length, mode,
                                    layer)
            return (h, c), aux

        if remat:
            policy = (jax.checkpoint_policies.checkpoint_dots
                      if self.remat_policy == "dots" else None)
            body = jax.checkpoint(body, prevent_cse=False, policy=policy)
        xs = (params["layers"], win, theta, jnp.arange(self.cfg.n_layers))
        (x, cache), aux = jax.lax.scan(body, (x, cache), xs)
        if cache is not None:
            cache = self._pin_cache(cache)
        return x, cache, jnp.sum(aux)

    def _embed_inputs(self, params, tokens, patch_embeds=None):
        x = C.embed(tokens, params["embed"], self.cfg, self.dist)
        if patch_embeds is not None:
            x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
        return x

    def loss(self, params, batch):
        """batch: tokens (b,s), labels (b,s), optional loss_mask (b,s),
        optional patch_embeds (b,P,d)."""
        cfg = self.cfg
        patches = batch.get("patch_embeds")
        x = self._embed_inputs(params, batch["tokens"], patches)
        positions = jnp.arange(x.shape[1])[None, :]
        x, _, aux = self._run_layers(x, params, positions,
                                     None, None, "train",
                                     remat=True)
        x = L.apply_norm(x, params["final_norm"], cfg)
        if patches is not None:
            x = x[:, patches.shape[1]:]
        logits = C.lm_logits(x, params["embed"], cfg, self.dist)
        loss = C.next_token_loss(logits, batch["labels"],
                                 batch.get("loss_mask"))
        metrics = {"xent": loss, "aux_loss": aux}
        if cfg.mtp_depth:
            mtp_loss = self._mtp_loss(params, x, batch)
            loss = loss + 0.3 * mtp_loss
            metrics["mtp"] = mtp_loss
        return loss + aux, metrics

    def _mtp_loss(self, params, h, batch):
        """Depth-1 multi-token prediction (DeepSeek-V3 §2.2, simplified to
        one extra block sharing the embedding/head)."""
        cfg = self.cfg
        emb_next = C.embed(jnp.roll(batch["labels"], -1, axis=1),
                           params["embed"], cfg, self.dist)
        hn = L.rmsnorm(h, params["mtp"]["norm"], cfg.norm_eps)
        x = jnp.concatenate([hn, emb_next], axis=-1) @ params["mtp"]["proj"]
        positions = jnp.arange(x.shape[1])[None, :]
        win, theta = layer_scalars(cfg)
        x, _, _ = self._layer(x, params["mtp"]["layer"], win[-1], theta[-1],
                              positions, None, None, "train")
        logits = C.lm_logits(x, params["embed"], cfg, self.dist)
        labels2 = jnp.roll(batch["labels"], -1, axis=1)
        mask = jnp.ones_like(labels2, jnp.float32).at[:, -2:].set(0.0)
        return C.next_token_loss(logits, labels2, mask)

    def prefill(self, params, tokens, max_len, patch_embeds=None):
        x = self._embed_inputs(params, tokens, patch_embeds)
        positions = jnp.arange(x.shape[1])[None, :]
        cache = self.init_cache(tokens.shape[0], max_len,
                                extra=0 if patch_embeds is None
                                else patch_embeds.shape[1])
        x, cache, _ = self._run_layers(x, params, positions, cache, None,
                                       "prefill")
        with jax.named_scope("head"):
            x = L.apply_norm(x, params["final_norm"], self.cfg)
            logits = C.lm_logits(x[:, -1:], params["embed"], self.cfg,
                                 self.dist)
        return logits, cache, jnp.full((), x.shape[1], jnp.int32)

    def decode(self, params, cache, tokens, length):
        """tokens (b,1); length scalar = #valid cache entries."""
        x = self._embed_inputs(params, tokens)
        x, cache, _ = self._run_layers(x, params, None, cache, length,
                                       "decode")
        with jax.named_scope("head"):
            x = L.apply_norm(x, params["final_norm"], self.cfg)
            logits = C.lm_logits(x, params["embed"], self.cfg, self.dist)
        return logits, cache, length + 1

    # -------------------------------------------------------------- caches

    def _pin_cache(self, cache):
        return jax.tree.map(lambda spec, c: self.dist.wsc(c, *spec),
                            self.cache_specs(), cache,
                            is_leaf=lambda x: isinstance(x, P))

    def cache_specs(self):
        """PartitionSpecs matching init_cache output."""
        dp = self.dist.batch_axes()
        kv = self.dist.kv_axes()
        if self.cfg.mla is not None:
            return {"ckv": P(None, dp, kv, None),
                    "krope": P(None, dp, kv, None)}
        return {"k": P(None, dp, kv, None, None),
                "v": P(None, dp, kv, None, None)}

    def init_cache(self, batch, max_len, extra=0):
        cfg = self.cfg
        S = max_len + extra
        Ln = cfg.n_layers
        if cfg.mla is not None:
            m = cfg.mla
            return {"ckv": jnp.zeros((Ln, batch, S, m.kv_lora_rank),
                                     self.dtype),
                    "krope": jnp.zeros((Ln, batch, S, m.qk_rope_head_dim),
                                       self.dtype)}
        hd = cfg.resolved_head_dim
        return {"k": jnp.zeros((Ln, batch, S, cfg.n_kv_heads, hd),
                               self.dtype),
                "v": jnp.zeros((Ln, batch, S, cfg.n_kv_heads, hd),
                               self.dtype)}
