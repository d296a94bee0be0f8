"""The layer scan's carried KV cache against the scan it replaced.

``DecoderLM`` carries the stacked cache through the layer scan, and each
layer writes only its new rows into it.  The reference below is the
earlier formulation, kept here and nowhere else: the cache enters the scan
as xs, one layer's slice at a time, and leaves it as ys; prefill pads each
layer's prompt K/V to the cache length, decode rewrites the layer's slice.
Prefill and a dozen decode steps must give the same logits and the same
final cache, for every kind of cache the decoder keeps.

The four-device test runs the served steps over the tensor-parallel
layout, whose cache is split over its sequence across the chips.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import attention as A
from repro.models import common as C
from repro.models import layers as L
from repro.models.factory import build_model
from repro.models.transformer import DecoderLM, layer_scalars
from repro.serving import ModelServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, PROMPT, STEPS = 2, 10, 12


class XsYsReference(DecoderLM):
    """The layer scan with the cache as xs/ys (one chip, no mesh)."""

    def _run_layers(self, x, params, positions, cache, length, mode,
                    remat=False):
        assert mode != "train"
        win, theta = layer_scalars(self.cfg)

        def body(h, xs):
            lp, w, t, ce = xs
            cfg, rs = self.cfg, C.residual_scale(self.cfg)
            a = L.apply_norm(h, lp["ln1"], cfg)
            if mode == "decode":
                a, ce = self._old_decode(a, lp["attn"], w, t, ce, length)
            else:
                a, ce = self._old_prefill(a, lp["attn"], w, t, positions, ce)
            h = h + a * rs
            f, aux = self._ffn(L.apply_norm(h, lp["ln2"], cfg), lp["ffn"],
                               mode)
            return h + f * rs, (ce, aux)

        x, (cache, aux) = jax.lax.scan(
            body, x, (params["layers"], win, theta, cache))
        return x, cache, jnp.sum(aux)

    def _old_prefill(self, x, ap, win, theta, positions, ce):
        cfg = self.cfg
        pad = lambda a: jnp.pad(a, [(0, 0), (0, ce[next(iter(ce))].shape[1]
                                             - a.shape[1])]
                                + [(0, 0)] * (a.ndim - 2))
        if cfg.mla is not None:
            out, (c_kv, k_rope) = A.mla_prefill(x, ap, cfg, positions)
            return out, {"ckv": pad(c_kv), "krope": pad(k_rope)}
        q, k, v = A.project_qkv(x, ap, cfg)
        if not cfg.no_rope:
            q = L.apply_rope(q, positions, theta)
            k = L.apply_rope(k, positions, theta)
        ce = {"k": pad(k), "v": pad(v)}
        k, v = A.repeat_kv(k, cfg.n_heads), A.repeat_kv(v, cfg.n_heads)
        cap = cfg.attn_logit_softcap
        if self.static_window:
            o = A.sliding_window_attention(q, k, v, window=self.static_window,
                                           softcap=cap)
        else:
            o = A.flash_attention(q, k, v, causal=True, window=win,
                                  softcap=cap)
        return o.reshape(x.shape[0], x.shape[1], -1) @ ap["wo"], ce

    def _old_decode(self, x, ap, win, theta, ce, length):
        cfg = self.cfg
        positions = jnp.full((x.shape[0], 1), length, jnp.int32)
        put = lambda c, r, at: jax.lax.dynamic_update_slice(
            c, r, (0, at) + (0,) * (r.ndim - 2))
        if cfg.mla is not None:
            c_kv, k_rope = A.mla_latents(x, ap, cfg, positions)
            ce = {"ckv": put(ce["ckv"], c_kv, length),
                  "krope": put(ce["krope"], k_rope, length)}
            return A.mla_decode(x, ap, cfg, ce["ckv"], ce["krope"],
                                length + 1, positions), ce
        q, k, v = A.project_qkv(x, ap, cfg)
        if not cfg.no_rope:
            q = L.apply_rope(q, positions, theta)
            k = L.apply_rope(k, positions, theta)
        S = ce["k"].shape[1]
        if self.window_cache:
            at, n_valid, win = jnp.mod(length, S), jnp.minimum(length + 1,
                                                               S), 0
        else:
            at, n_valid = length, length + 1
        ce = {"k": put(ce["k"], k, at), "v": put(ce["v"], v, at)}
        o = A.decode_attention(q, ce["k"], ce["v"], n_valid, window=win,
                               softcap=cfg.attn_logit_softcap)
        return o.reshape(x.shape[0], 1, -1) @ ap["wo"], ce


def _smoke(case):
    """(config, model knobs, max_len, prefix patches) of each case."""
    h2o = get_smoke("h2o-danube-3-4b")
    if case == "gqa":
        return h2o, {}, 32, 0
    if case == "mha":
        return h2o.replace(n_kv_heads=h2o.n_heads), {}, 32, 0
    if case == "ring":          # 10 + 12 positions wrap a 16-slot ring
        return h2o, {"window_cache": True}, h2o.sliding_window, 0
    if case == "mla":
        cfg = get_smoke("deepseek-v3-671b")
        return cfg.replace(moe=dataclasses.replace(
            cfg.moe, capacity_factor=16.0)), {}, 32, 0
    cfg = get_smoke("internvl2-76b")
    return cfg, {}, 32, cfg.n_vision_patches


def _serve(model, params, prompt, max_len, patches):
    """Prefill, then STEPS greedy decode steps; the logits of each step
    and the final cache."""
    kw = {} if patches is None else {"patch_embeds": patches}
    logits, cache, length = jax.jit(
        lambda p, t: model.prefill(p, t, max_len, **kw))(params, prompt)
    decode = jax.jit(model.decode)
    out = [logits[:, -1]]
    for _ in range(STEPS):
        nxt = jnp.argmax(out[-1], -1)[:, None].astype(jnp.int32)
        logits, cache, length = decode(params, cache, nxt, length)
        out.append(logits[:, -1])
    return np.stack([np.asarray(o, np.float32) for o in out], 1), cache


@pytest.mark.parametrize("served", [False, True])
@pytest.mark.parametrize("case", ["gqa", "mha", "ring", "mla", "vlm_prefix"])
def test_carried_cache_matches_xs_ys_scan(case, served):
    """``served``: a ``ModelServer`` names the weights' device to the
    model, whose cache writes then take the layout that device stores the
    cache in; else the writes ask no layout."""
    cfg, knobs, max_len, n_patches = _smoke(case)
    model, ref = build_model(cfg), XsYsReference(cfg)
    for m in (model, ref):
        for k, v in knobs.items():
            setattr(m, k, v)
    params = model.init(jax.random.PRNGKey(3))
    if served:
        ModelServer(model, params, max_len=max_len)
        assert model.cache_device == jax.devices()[0]
    else:
        assert model.cache_device is None
    prompt = jax.random.randint(jax.random.PRNGKey(4), (ROWS, PROMPT), 1,
                                cfg.vocab_size)
    patches = None
    if n_patches:
        patches = jax.random.normal(jax.random.PRNGKey(5),
                                    (ROWS, n_patches, cfg.d_model),
                                    jnp.bfloat16)
    got, got_cache = _serve(model, params, prompt, max_len, patches)
    want, want_cache = _serve(ref, params, prompt, max_len, patches)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    assert got_cache.keys() == want_cache.keys()
    for k in want_cache:
        assert got_cache[k].shape == want_cache[k].shape
        np.testing.assert_allclose(np.asarray(got_cache[k], np.float32),
                                   np.asarray(want_cache[k], np.float32),
                                   rtol=2e-2, atol=2e-2)


# The tensor-parallel cell's layout at the smoke widths on a (1, 4)
# ("data", "model") mesh: 32 cache positions, 8 on each chip.  Two waves:
# a 6-token prompt whose six decode steps write positions 6-11, across
# the boundary of chips 0 and 1; and a 12-token prompt, longer than a
# chip's share, whose steps write positions 12-17, into chips 1 and 2.
FOUR = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from chipbench import harness
from chipbench.tests.smoke import SMOKE_CONFIG
from repro.distribution.context import make_context
from repro.launch.mesh import make_mesh
from repro.launch.serve import init_params
from repro.models.factory import build_model
from repro.serving import ModelServer

tp4 = harness.load_json(sys.argv[1] + "/chipbench/configs/"
                        "mistral-nemo-12b-tp4.json")
conf = dict(SMOKE_CONFIG, arch=tp4["arch"], rope_theta=tp4["rope_theta"],
            sliding_window=None, num_attention_heads=8,
            num_key_value_heads=4, head_dim=8)
cfg = harness.arch_config(conf)
mesh = make_mesh((1, 4), ("data", "model"), devices=jax.devices()[:4])
model = build_model(cfg, make_context(mesh))
params = init_params(model, 2**31 + 17)
sharded = ModelServer(model, params, max_len=32)
plain = ModelServer(build_model(cfg),
                    jax.device_put(params, jax.devices()[0]), max_len=32)
rng = np.random.default_rng(5)

def serve(server, prompt, steps=6):
    logits, cache, length = server._prefill_fn(server.params,
                                               jnp.asarray(prompt))
    out, caches = [np.asarray(logits[:, -1], np.float32)], [cache]
    for _ in range(steps):
        nxt = jnp.asarray(out[-1].argmax(-1)[:, None].astype(np.int32))
        logits, cache, length = server._decode_fn(server.params, cache, nxt,
                                                  length)
        out.append(np.asarray(logits[:, -1], np.float32))
        caches.append(cache)
    return np.stack(out, 1), caches, int(length)

specs = model.cache_specs()
waves = []
for n in (6, 12):
    prompt = rng.integers(1, 512, (2, n)).astype(np.int32)
    got, caches, length = serve(sharded, prompt)
    want, plain_caches, _ = serve(plain, prompt)
    waves.append({
        "length": length,
        "shardings": [all(c[k].sharding.is_equivalent_to(
            NamedSharding(mesh, specs[k]), c[k].ndim) for k in specs)
            for c in caches],
        "logit_gap": float(np.abs(got - want).max()),
        "logit_scale": float(np.abs(want).max()),
        "cache_gap": max(float(np.abs(
            np.asarray(caches[-1][k], np.float32)
            - np.asarray(plain_caches[-1][k], np.float32)).max())
            for k in specs)})
print(json.dumps({"decode_compiles": sharded._decode_fn._cache_size(),
                  "waves": waves}))
"""


def test_split_cache_serves_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", FOUR, ROOT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    # one decode program for both waves: prefill's cache and every decode
    # step's come back with the one sharding the step was compiled for
    assert r["decode_compiles"] == 1
    assert [w["length"] for w in r["waves"]] == [12, 18]
    for w in r["waves"]:
        assert all(w["shardings"]), w["shardings"]
        assert w["logit_gap"] < 0.02 * w["logit_scale"], w
        assert w["cache_gap"] < 0.05, w
