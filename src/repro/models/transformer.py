"""Decoder-only LM assembly (dense / MoE / VLM-prefix / MLA), scanned.

One ``lax.scan`` over stacked layer params keeps HLO size O(1) in depth.
Layer heterogeneity that only changes *numbers* (gemma3 local/global window
+ rope theta) rides along as per-layer scalar xs; heterogeneity that changes
*structure* (Jamba) lives in hybrid.py instead.

KV caches are scan xs/ys with layout (L, b, S, h, hd) sharded
(None, dp, `model`, None, None) — the flash-decoding layout (DESIGN.md §5).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
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

    def _attention_full(self, x, ap, win, theta, positions, cache_entry,
                        length):
        """Train/prefill attention. cache_entry None (train) or dict to
        fill (prefill). Returns (out, new_cache_entry)."""
        cfg, dist = self.cfg, self.dist
        dp, hshard = self._attn_specs()
        kv = dist.kv_axes()
        if cfg.mla is not None:
            out, (c_kv, k_rope) = A.mla_prefill(x, ap, cfg, positions)
            new_cache = None
            if cache_entry is not None:
                S = cache_entry["ckv"].shape[1]
                pad = S - c_kv.shape[1]
                new_cache = {
                    "ckv": dist.wsc(jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0))),
                                    dp, kv, None),
                    "krope": dist.wsc(
                        jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0))),
                        dp, kv, None),
                }
            return out, new_cache
        q, k, v = A.project_qkv(x, ap, cfg)
        if not cfg.no_rope:
            q = L.apply_rope(q, positions, theta)
            k = L.apply_rope(k, positions, theta)
        new_cache = None
        if cache_entry is not None:
            S = cache_entry["k"].shape[1]
            pad = S - k.shape[1]
            new_cache = {
                "k": dist.wsc(jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
                              dp, kv, None, None),
                "v": dist.wsc(jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))),
                              dp, kv, None, None),
            }
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
        return out, new_cache

    def _attention_decode(self, x, ap, win, theta, cache_entry, length):
        cfg, dist = self.cfg, self.dist
        dp = dist.batch_axes()
        kv = dist.kv_axes()
        positions = jnp.full((x.shape[0], 1), length, jnp.int32)
        if cfg.mla is not None:
            c_kv, k_rope = A.mla_latents(x, ap, cfg, positions)
            ckv_c = jax.lax.dynamic_update_slice(
                cache_entry["ckv"], c_kv, (0, length, 0))
            krope_c = jax.lax.dynamic_update_slice(
                cache_entry["krope"], k_rope, (0, length, 0))
            ckv_c = dist.wsc(ckv_c, dp, kv, None)
            krope_c = dist.wsc(krope_c, dp, kv, None)
            if self.sp_decode and dist.active:
                out = A.mla_decode_sp(x, ap, cfg, ckv_c, krope_c,
                                      length + 1, positions, dist)
            else:
                out = A.mla_decode(x, ap, cfg, ckv_c, krope_c, length + 1,
                                   positions)
            return out, {"ckv": ckv_c, "krope": krope_c}
        q, k, v = A.project_qkv(x, ap, cfg)
        if not cfg.no_rope:
            q = L.apply_rope(q, positions, theta)
            k = L.apply_rope(k, positions, theta)
        S_cache = cache_entry["k"].shape[1]
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
        k_c = jax.lax.dynamic_update_slice(cache_entry["k"], k,
                                           (0, write_at, 0, 0))
        v_c = jax.lax.dynamic_update_slice(cache_entry["v"], v,
                                           (0, write_at, 0, 0))
        k_c = dist.wsc(k_c, dp, kv, None, None)
        v_c = dist.wsc(v_c, dp, kv, None, None)
        if self.sp_decode and dist.active:
            o = A.decode_attention_sp(q, k_c, v_c, n_valid, dist,
                                      window=win,
                                      softcap=cfg.attn_logit_softcap,
                                      n_heads=cfg.n_heads)
        else:
            o = A.decode_attention(q, k_c, v_c, n_valid, window=win,
                                   softcap=cfg.attn_logit_softcap)
        out = o.reshape(x.shape[0], 1, -1) @ ap["wo"]
        return dist.wsc(out, dp, None, None), {"k": k_c, "v": v_c}

    def _ffn(self, x, fp, mode="train"):
        if self.cfg.moe is not None:
            return self._moe(x, fp, mode)
        return L.apply_mlp(x, fp, self.cfg.act), jnp.float32(0.0)

    def _layer(self, x, lp, win, theta, positions, cache_entry, length,
               mode):
        # the scopes name the device's operations by model part (their
        # op_name metadata: ``.../attention/...``, ``.../mlp/...``)
        cfg = self.cfg
        rs = C.residual_scale(cfg)
        with jax.named_scope("attention"):
            h = L.apply_norm(x, lp["ln1"], cfg)
            if mode == "decode":
                attn, new_cache = self._attention_decode(
                    h, lp["attn"], win, theta, cache_entry, length)
            else:
                attn, new_cache = self._attention_full(
                    h, lp["attn"], win, theta, positions, cache_entry,
                    length)
        x = x + attn * rs
        with jax.named_scope("mlp"):
            h = L.apply_norm(x, lp["ln2"], cfg)
            ffn, aux = self._ffn(h, lp["ffn"], mode)
        x = x + ffn * rs
        return x, new_cache, aux

    # ------------------------------------------------------------- forwards

    def _run_layers(self, x, params, positions, cache, length, mode,
                    remat=False):
        win, theta = layer_scalars(self.cfg)

        def body(carry, xs):
            h = carry
            lp, w, t, ce = xs
            if mode == "train":
                ce = None                      # placeholder xs, no cache
            h, new_ce, aux = self._layer(h, lp, w, t, positions, ce, length,
                                         mode)
            return h, (new_ce, aux)

        if remat:
            policy = (jax.checkpoint_policies.checkpoint_dots
                      if self.remat_policy == "dots" else None)
            body = jax.checkpoint(body, prevent_cse=False, policy=policy)
        xs = (params["layers"], win, theta, cache)
        x, (new_cache, aux) = jax.lax.scan(body, x, xs)
        return x, new_cache, jnp.sum(aux)

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
                                     self._null_cache(), None, "train",
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

    def _null_cache(self):
        return jnp.zeros((self.cfg.n_layers, 0), jnp.int32)

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
