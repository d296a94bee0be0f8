"""The plain reference holds the same weights as the served model and
agrees with its logits to bfloat16 rounding, at the smoke size."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.reference import dense
from chipbench.tests.smoke import SMOKE_CONFIG as C

SEED = 2 ** 31 + 41


@pytest.fixture(scope="module")
def served():
    from repro.launch.serve import init_params
    from repro.models.factory import build_model
    model = build_model(harness.arch_config(C))
    return model, init_params(model, SEED)


def test_reference_weights_are_the_served_weights(served):
    _, p = served
    keys = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(SEED), 17),
                            C["num_hidden_layers"])
    for layer, key in enumerate(keys):
        w = dense.layer_weights(C, key)
        for name, group in [("wq", "attn"), ("wk", "attn"), ("wv", "attn"),
                            ("wo", "attn"), ("gate", "ffn"), ("up", "ffn"),
                            ("down", "ffn")]:
            got = p["layers"][group][name][layer].astype(jnp.float32)
            assert bool(jnp.array_equal(w[name], got)), (layer, name)


def test_reference_logits_agree_with_prefill_and_decode(served):
    model, p = served
    toks = np.random.default_rng(0).integers(1, C["vocab_size"], (2, 20),
                                             dtype=np.int32)
    logits, cache, length = model.prefill(p, jnp.asarray(toks), 64)
    got = [np.asarray(logits[:, -1], np.float32)]
    nxt = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    seq = np.concatenate([toks, np.asarray(nxt)], 1)
    logits, cache, length = model.decode(p, cache, nxt, length)
    got.append(np.asarray(logits[:, -1], np.float32))
    want = dense.logits(C, SEED, seq, np.array([[19, 20]] * 2, np.int32))[0]
    for j in range(2):
        err = np.abs(got[j] - want[:, j]).max()
        # bf16 weights and activations: a few 2^-8 steps of |logit| ~ 3
        assert err < 0.1, err
    assert np.abs(want).max() > 1.0
