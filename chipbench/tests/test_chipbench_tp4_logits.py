"""The tensor-parallel configuration's served prefill-then-decode logits
on four virtual CPU devices, at the smoke widths on its (1, 4) ("data",
"model") mesh, against the plain reference's full forward pass on the
same seeded weights: with the head size at hidden_size / heads and, as
mistral-nemo sets it, apart from it."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import HERE, ROOT

# the tp4 layout at the smoke widths; argv[3] is the head size, which
# mistral-nemo sets apart from hidden_size / heads (128 against 160)
LOGITS = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax, jax.numpy as jnp, numpy as np
from chipbench import harness
from chipbench.reference import dense
from chipbench.tests.smoke import SMOKE_CONFIG
from repro.distribution.context import make_context
from repro.launch.mesh import make_mesh
from repro.launch.serve import init_params
from repro.models.factory import build_model
from repro.serving import ModelServer

tp4 = harness.load_json(sys.argv[2])
conf = dict(SMOKE_CONFIG, arch=tp4["arch"], mesh=tp4["mesh"],
            rope_theta=tp4["rope_theta"], sliding_window=None,
            num_attention_heads=8, num_key_value_heads=4,
            head_dim=int(sys.argv[3]))
seed, rows, prompt, steps = 2**31 + 77, 2, 12, 6
mesh = make_mesh(tuple(conf["mesh"]["shape"]), tuple(conf["mesh"]["axes"]),
                 devices=jax.devices()[:4])
model = build_model(harness.arch_config(conf), make_context(mesh))
server = ModelServer(model, init_params(model, seed),
                     max_len=conf["max_len"])
seq = np.random.default_rng(seed).integers(
    1, conf["vocab_size"], (rows, prompt)).astype(np.int32)
logits, cache, length = server._prefill_fn(server.params, jnp.asarray(seq))
served = [np.asarray(logits[:, -1], np.float32)]
for _ in range(steps):
    nxt = served[-1].argmax(-1).astype(np.int32)[:, None]
    seq = np.concatenate([seq, nxt], 1)
    logits, cache, length = server._decode_fn(server.params, cache,
                                              jnp.asarray(nxt), length)
    served.append(np.asarray(logits[:, -1], np.float32))
at = np.tile(np.arange(prompt - 1, prompt + steps), (rows, 1))
ref, fp8 = dense.logits(conf, seed, seq, at, (None, "fp8"))
rel = lambda x: float(np.linalg.norm(x - ref) / np.linalg.norm(ref))
devices = {d for leaf in jax.tree.leaves(server.params)
           for d in leaf.sharding.device_set}
print(json.dumps({"devices": len(devices), "served": rel(np.stack(served, 1)),
                  "fp8": rel(fp8)}))
"""
# relative L2 distance of the served logits from the float32 reference:
# the program holds weights and activations in bfloat16 (8 significant
# bits) and sums the row-parallel partial products over the chips in
# bfloat16, which reads about 1e-2 here; 2^-5 admits that and a few times
# more, and the reference's own fp8 control (3 significant bits) reads
# 0.12-0.15, so a path that dropped below the precision the configuration
# states, or lost a chip's partial sum, fails it
LOGIT_TOL = 2.0 ** -5


@pytest.mark.parametrize("head_dim", [8, 16],
                         ids=["hd=d/heads", "hd!=d/heads"])
def test_tp4_served_logits_match_the_reference(head_dim):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    cfg = os.path.join(HERE, "configs", "mistral-nemo-12b-tp4.json")
    p = subprocess.run([sys.executable, "-c", LOGITS, ROOT, cfg,
                        str(head_dim)], env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["devices"] == 4
    assert r["served"] < LOGIT_TOL, r
    assert r["fp8"] > LOGIT_TOL, r
