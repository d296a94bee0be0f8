"""Compiles for one TPU v5e chip that is described, not attached.

The chip's compiler is installed with jax and refuses what interpret mode
accepts: Pallas blocks that are not tile-aligned, unaligned dynamic loads,
programs that do not fit the device's memory.  These tests keep the main
path's kernels at their real widths, the served decode step of
h2o-danube-3-4b at full width, and mistral-nemo-12b's tensor-parallel
decode step over a described four-chip host, inside what the chip
accepts.  Nothing runs.

Only this file describes the topology, and only inside the fixture below:
one process at a time may load the TPU's library, so describing it at
import would make the test workers collect different tests.
"""
from __future__ import annotations

import os

from collections import Counter

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from chipbench import collectives, harness
from chipbench import HERE as CHIPBENCH
from repro.configs import get_config
from repro.distribution.context import make_context
from repro.distribution.sharding import param_shardings
from repro.launch.mesh import make_mesh
from repro.kernels.mamba_scan.kernel import selective_scan_pallas
from repro.kernels.rwkv6.kernel import wkv6_pallas
from repro.models.factory import build_model
from repro.serving import ModelServer

HBM_BYTES = 16 * 1024 ** 3          # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001 — skip reason
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The (1, 4) ("data", "model") mesh of the tensor-parallel cell, on
    the four chips of the described host."""
    return make_mesh((1, 4), ("data", "model"), devices=topo.devices)


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


def h2o_server(one_chip, batch, max_len):
    cfg = get_config("h2o-danube-3-4b")
    model = build_model(cfg)
    place = lambda tree: jax.tree.map(
        lambda s: on_chip(one_chip, s.shape, s.dtype), tree)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: model.init_cache(batch, max_len)))
    return cfg, ModelServer(model, params, max_len=max_len), params, cache


def entry_text(text):
    return text[text.index("\nENTRY"):]


@pytest.mark.parametrize("batch,max_len", [(4, 2048), (8, 4096)])
def test_h2o_danube_decode_fits_one_chip(one_chip, batch, max_len):
    """The served decode step at full width: weights, donated KV cache and
    temporaries within 16 GiB.  At the benchmark cells' shape (batch 8,
    max_len 4096) the step also holds no float32 copy of the cache, at the
    KV heads' count or repeated to the query heads', and the layer loop
    carries the donated cache itself: no copy of the stacked cache into or
    out of the loop, no second stack, temporaries of 0.35 MB (3.2 GB while
    the cache was the loop's xs and ys), and the whole cache aliased from
    input to output."""
    cfg, server, params, cache = h2o_server(one_chip, batch, max_len)
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
        stack = f"bf16[{cfg.n_layers},{batch},{max_len},{cfg.n_kv_heads},{hd}]"
        for line in entry_text(text).splitlines():
            assert not (stack in line and (" copy(" in line
                                           or "AllocateBuffer" in line)), line
        assert mem.temp_size_in_bytes < 0.5e9, mem.temp_size_in_bytes
        assert mem.alias_size_in_bytes >= 3019898880, mem.alias_size_in_bytes


def test_h2o_danube_prefill_writes_the_prompt_in_place(one_chip):
    """The served prefill at the code cell's shape (batch 8, 1500-token
    prompts, max_len 4096): each layer writes its prompt's K/V, unpadded,
    into the stacked cache that becomes the step's output.  No layer's K/V
    is padded to max_len, nothing writes a whole stack inside a fusion, and
    the stack is never copied.  Its 3.59 GB of temporaries are the prefill
    attention's and the head's, not the cache's (3.55 GB while each layer's
    padded K/V was restacked as the loop's ys)."""
    batch, prompt, max_len = 8, 1500, 4096
    cfg, server, params, _ = h2o_server(one_chip, batch, max_len)
    compiled = server._prefill_fn.lower(
        params, on_chip(one_chip, (batch, prompt), jnp.int32)).compile()
    hd, kv = cfg.resolved_head_dim, cfg.n_kv_heads
    stack = f"bf16[{cfg.n_layers},{batch},{max_len},{kv},{hd}]"
    text = compiled.as_text()
    assert f"bf16[1,{batch},{max_len},{kv},{hd}]" not in text
    writes = [line for line in text.splitlines()
              if f"{stack}{{" in line and "dynamic-update-slice(" in line]
    assert len(writes) == 2, writes                 # K and V, once a layer
    assert not any("ROOT" in line for line in writes), writes
    assert f"{stack}{{" not in "".join(
        line for line in text.splitlines() if " copy(" in line)
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes > 3.0e9         # the stack is the output
    assert mem.temp_size_in_bytes < 3.6e9, mem.temp_size_in_bytes


def test_nemo_tp4_decode_fits_four_chips(four_chips):
    """The tensor-parallel cell's served decode step (mistral-nemo-12b at
    published widths, batch 8, max_len 4096) as the harness places it:
    parameters by ``param_shardings``, the cache by ``cache_specs``.  Per
    chip it fits in 16 GiB; the K/V cache stays split over the chips
    (no array of one layer's whole cache); and the collectives of the
    layer loop's body are pinned, so that a change of layout updates this
    test on purpose.  The layer loop carries the donated cache split as it
    is stored and each chip writes the rows it holds: no copy of the stacked
    cache, no collective over it, temporaries of 0.35 MB a chip (1.34 GB
    while the cache was the loop's xs and ys)."""
    conf = harness.load_json(os.path.join(
        CHIPBENCH, "configs", "mistral-nemo-12b-tp4.json"))
    cfg = harness.arch_config(conf)
    batch, max_len = conf["batch"], conf["max_len"]
    mesh = four_chips
    model = build_model(cfg, make_context(mesh))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, param_shardings(model, shapes))
    specs = model.cache_specs()
    cache = {k: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                     sharding=NamedSharding(mesh, specs[k]))
             for k, s in jax.eval_shape(
                 lambda: model.init_cache(batch, max_len)).items()}
    whole = NamedSharding(mesh, P())
    server = ModelServer(model, params, max_len=max_len)
    compiled = server._decode_fn.lower(
        params, cache,
        jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=whole),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=whole)).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes > 6e9    # a quarter of 24.5 GB, and more
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB"
    hd = cfg.resolved_head_dim
    text = compiled.as_text()
    assert f"bf16[{batch},{max_len},{cfg.n_kv_heads},{hd}]" not in text
    assert mem.temp_size_in_bytes < 0.3e9, mem.temp_size_in_bytes
    stacks = [f"bf16[{cfg.n_layers},{batch},{S},{cfg.n_kv_heads},{hd}]"
              for S in (max_len, max_len // 4)]
    for line in text.splitlines():
        if any(c in line for c in ("all-gather", "all-reduce", "collective",
                                   "all-to-all", "reduce-scatter")):
            assert not any(s in line for s in stacks), line
    for line in entry_text(text).splitlines():
        assert not (any(s in line for s in stacks) and " copy(" in line), line
    (module,) = compiled.runtime_executable().hlo_modules()
    proto = module.as_serialized_hlo_module_proto()
    ops = collectives.collective_ops(proto)
    entry = collectives.entry_computation(proto)
    body = Counter(k for c, _, k in ops if c != entry)
    # the layer loop's body: q gathered over the sequence shards (an async
    # start, a fusion and a done), the softmax merged across them (three
    # all-reduces), the row-parallel sums after wo and down (two); outside
    # it the vocab-sharded embedding's one all-reduce
    assert body == {"all-gather": 3, "all-reduce": 5}, ops
    assert len(ops) - sum(body.values()) == 1, ops
