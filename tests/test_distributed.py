"""Multi-device tests (8 fake CPU devices via a subprocess, since the
main pytest process is pinned to 1 device): numeric equivalence of the
distributed paths vs the single-device reference, and representative
(arch x shape) cell compiles on a small mesh."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

# minutes of XLA compile work per test; the core rFaaS suite skips
# these via -m "not slow" (see ROADMAP.md)
pytestmark = pytest.mark.slow

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, timeout=560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env, cwd=ROOT)
    assert out.returncode == 0, f"STDOUT:{out.stdout}\nSTDERR:{out.stderr}"
    return out.stdout


PREAMBLE = """
import dataclasses
import jax, jax.numpy as jnp
from repro.configs import get_smoke
from repro.distribution.context import make_context
from repro.launch.mesh import make_mesh
from repro.models.factory import build_model
mesh = make_mesh((2, 4), ("data", "model"))
"""


def test_sp_decode_and_full_ep_match_reference():
    run_sub(PREAMBLE + """
for arch, knobs in [("mistral-nemo-12b", {"sp_decode": True}),
                    ("deepseek-v3-671b", {"sp_decode": True,
                                          "moe_full_ep": True})]:
    cfg = get_smoke(arch)
    if cfg.moe:
        cfg = cfg.replace(moe=dataclasses.replace(
            cfg.moe, capacity_factor=16.0))   # no drops: exact comparison
    ref = build_model(cfg)
    params = ref.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0,
                              cfg.vocab_size)
    _, c_r, l_r = jax.jit(lambda p, t: ref.prefill(p, t, 16))(params, toks)
    lr, _, _ = jax.jit(ref.decode)(params, c_r, toks[:, :1], l_r)
    m2 = build_model(cfg, make_context(mesh, kv_seq=("model",)))
    for k, v in knobs.items():
        setattr(m2, k, v)
    with mesh:
        _, c2, l2 = jax.jit(lambda p, t: m2.prefill(p, t, 16))(params,
                                                               toks)
        l2_, _, _ = jax.jit(m2.decode)(params, c2, toks[:, :1], l2)
    err = float(jnp.max(jnp.abs(l2_.astype(jnp.float32)
                                - lr.astype(jnp.float32))))
    assert err < 0.05, f"{arch}: {err}"
print("OK")
""")


def test_train_loss_matches_across_mesh():
    """One train loss value: mesh vs no-mesh (dense arch, exact routing
    not involved)."""
    run_sub(PREAMBLE + """
cfg = get_smoke("mistral-nemo-12b")
ref = build_model(cfg)
params = ref.init(jax.random.PRNGKey(0))
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                      cfg.vocab_size)}
batch["labels"] = jnp.roll(batch["tokens"], -1, 1)
l_ref, _ = jax.jit(lambda p: ref.loss(p, batch))(params)
m2 = build_model(cfg, make_context(mesh))
with mesh:
    l2, _ = jax.jit(lambda p: m2.loss(p, batch))(params)
assert abs(float(l_ref) - float(l2)) < 0.05, (float(l_ref), float(l2))
print("OK")
""")


@pytest.mark.parametrize("arch,shape", [
    ("mixtral-8x7b", "train_4k"),
    ("deepseek-v3-671b", "decode_32k"),
    ("jamba-1.5-large-398b", "long_500k"),
    ("rwkv6-1.6b", "decode_32k"),
    ("whisper-tiny", "prefill_32k"),
    ("internvl2-76b", "train_4k"),
])
def test_cell_compiles_smoke_mesh(arch, shape):
    """Representative cells lower+compile on the 8-device mesh using the
    SMOKE configs (the full 512-device pass is launch.dryrun)."""
    run_sub(f"""
import jax
from repro.launch.mesh import make_mesh
from repro.launch.specs import build_cell
mesh = make_mesh((2, 4), ("data", "model"))
cell = build_cell("{arch}", "{shape}", mesh, smoke=True)
with mesh:
    comp = jax.jit(cell.step_fn, in_shardings=cell.in_shardings,
                   donate_argnums=cell.donate).lower(*cell.args).compile()
assert comp is not None
print("OK")
""")


def test_gpipe_forward_matches_sequential():
    """GPipe pipeline over a 4-way stage axis == sequential stage
    application (bubble only costs time, never correctness)."""
    run_sub("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.training.pipeline import gpipe_forward

mesh = make_mesh((4,), ("stage",))
S, M, mb, d = 4, 6, 2, 16
key = jax.random.PRNGKey(0)
W = jax.random.normal(key, (S, d, d)) * 0.3
bvec = jax.random.normal(jax.random.fold_in(key, 1), (S, d)) * 0.1
params = {"w": W, "b": bvec}
xs = jax.random.normal(jax.random.fold_in(key, 2), (M, mb, d))

def stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])

with mesh:
    out = jax.jit(lambda p, x: gpipe_forward(stage_fn, p, x, mesh=mesh,
                                             axis="stage"))(params, xs)
# sequential reference
ref = xs
for s in range(S):
    ref = jnp.tanh(ref @ W[s] + bvec[s])
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           rtol=2e-5, atol=2e-5)
print("OK")
""")
