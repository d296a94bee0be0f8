"""A whole run on the CPU at the smoke size, with the look for a chip
skipped: sound, it comes out correct; with the timed path broken
underneath, or with the lower-precision control in the program's place,
``correct`` comes out false."""
import jax
import jax.numpy as jnp

from chipbench import harness
from chipbench.tests.smoke import smoke_cell
from repro.serving import engine as E

SECONDS = 1.5


def run(seed, **kw):
    return harness.run_cell(smoke_cell(), seed, SECONDS, False,
                            require_chip=False, log=lambda s: None, **kw)


def test_sound_run_is_correct():
    out = run(2 ** 31 + 21)
    r = out.result
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())


def test_timed_path_runs_the_programs_own_library(monkeypatch):
    made = []
    orig = E.ModelServer.make_library

    def make_library(self, *a, **kw):
        lib = orig(self, *a, **kw)
        made.append(lib)
        return lib

    monkeypatch.setattr(E.ModelServer, "make_library", make_library)
    assert run(2 ** 31 + 24).result["correct"]
    assert len(made) == 1
    assert made[0].symbols == ["close_session", "decode", "prefill"]


def test_altered_token_is_caught(monkeypatch):
    orig = E.ModelServer.decode

    def decode(self, payload):
        out = orig(self, payload)
        tok = out["next_token"].copy()
        tok[0] = (tok[0] + 1) % self.model.cfg.vocab_size
        return dict(out, next_token=tok)

    monkeypatch.setattr(E.ModelServer, "decode", decode)
    assert not run(2 ** 31 + 22).result["correct"]


def test_decode_that_leaves_its_cache_unchanged_is_caught(monkeypatch):
    orig = E.ModelServer.decode

    def decode(self, payload):
        sid = int(payload["sid"])
        cache, _ = self._sessions[sid]
        kept = jax.tree.map(jnp.copy, cache)      # the step donates it
        out = orig(self, payload)
        with self._lock:
            self._sessions[sid] = (kept, self._sessions[sid][1])
        return out

    monkeypatch.setattr(E.ModelServer, "decode", decode)
    assert not run(2 ** 31 + 23).result["correct"]
