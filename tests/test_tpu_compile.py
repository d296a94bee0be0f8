"""Compiles for one TPU v5e chip that is described, not attached.

The chip's compiler is installed with jax and refuses what interpret mode
accepts: Pallas blocks that are not tile-aligned, unaligned dynamic loads,
programs that do not fit the device's memory.  These tests keep the main
path's kernels at their real widths, and the served decode step of
h2o-danube-3-4b at full width, inside what the chip accepts.  Nothing
runs.

Only this file describes the topology, and only inside the fixture below:
one process at a time may load the TPU's library, so describing it at
import would make the test workers collect different tests.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.mamba_scan.kernel import selective_scan_pallas
from repro.kernels.rwkv6.kernel import wkv6_pallas
from repro.models.factory import build_model
from repro.serving import ModelServer

HBM_BYTES = 16 * 1024 ** 3          # one TPU v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001 — skip reason
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def on_chip(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_wkv6_compiles_at_rwkv6_width(one_chip):
    cfg = get_config("rwkv6-1.6b")
    b, s, H, hd = 2, 512, cfg.n_heads, cfg.rwkv.head_dim
    x = on_chip(one_chip, (b, H, s, hd))
    compiled = wkv6_pallas.lower(
        x, x, x, x, on_chip(one_chip, (H, hd)),
        on_chip(one_chip, (b, H, hd, hd), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_selective_scan_compiles_at_jamba_width(one_chip):
    cfg = get_config("jamba-1.5-large-398b")
    b, s = 1, 512
    di, N = cfg.mamba.expand * cfg.d_model, cfg.mamba.d_state
    x = on_chip(one_chip, (b, s, di))
    bc = on_chip(one_chip, (b, s, N))
    compiled = selective_scan_pallas.lower(
        x, x, on_chip(one_chip, (di, N), jnp.float32), bc, bc,
        on_chip(one_chip, (di,), jnp.float32),
        on_chip(one_chip, (b, di, N), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch,max_len", [(4, 2048), (8, 4096)])
def test_h2o_danube_decode_fits_one_chip(one_chip, batch, max_len):
    """The served decode step at full width: weights, donated KV cache and
    temporaries within 16 GiB.  At the benchmark cells' shape (batch 8,
    max_len 4096) the step also holds no float32 copy of the cache, at the
    KV heads' count or repeated to the query heads'."""
    cfg = get_config("h2o-danube-3-4b")
    model = build_model(cfg)
    place = lambda tree: jax.tree.map(
        lambda s: on_chip(one_chip, s.shape, s.dtype), tree)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: model.init_cache(batch, max_len)))
    server = ModelServer(model, params, max_len=max_len)
    compiled = server._decode_fn.lower(
        params, cache, on_chip(one_chip, (batch, 1), jnp.int32),
        on_chip(one_chip, (), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes > 7.5e9       # the whole model is there
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB"
    if (batch, max_len) == (8, 4096):
        hd = cfg.resolved_head_dim
        text = compiled.as_text()
        for heads in (cfg.n_heads, cfg.n_kv_heads):
            assert f"f32[{batch},{max_len},{heads},{hd}]" not in text
        assert mem.temp_size_in_bytes < 3.5e9, mem.temp_size_in_bytes
