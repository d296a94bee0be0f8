"""Serving integration: batched generation through rFaaS leases, hot KV
residency, straggler backups, fault recovery."""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import jax
import pytest

from repro.configs import get_smoke
from repro.core import (BatchSystem, Invoker, Ledger, ResourceManager)
from repro.core.executor import ExecutorCrash
from repro.models.factory import build_model
from repro.serving import ModelServer, ServeEngine
from repro.serving.engine import backup_submit


def make_llm_stack(arch="mistral-nemo-12b", **kw):
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    server = ModelServer(model, params, max_len=48)
    lib = server.make_library()
    ledger = Ledger()
    rm = ResourceManager(n_replicas=2)
    bs = BatchSystem(rm, ledger, n_nodes=2, workers_per_node=2,
                     hot_period=5.0, **kw)
    bs.release_idle()
    inv = Invoker("serve", rm, lib, seed=0)
    inv.allocate(1)
    return cfg, server, inv, ledger


def test_batched_generation_completes():
    cfg, server, inv, ledger = make_llm_stack()
    engine = ServeEngine(inv, batch_size=3)
    rng = np.random.default_rng(0)
    reqs = [engine.enqueue(rng.integers(1, cfg.vocab_size, size=5),
                           max_new_tokens=4) for _ in range(7)]
    done = engine.run()
    assert len(done) == 7
    for r in done:
        assert len(r.tokens_out) == 4
        assert r.latency is not None and r.latency > 0
        assert r.ttft is not None and r.ttft <= r.latency
    assert sum(len(r.tokens_out) for r in done) == 28
    assert max(r.t_done for r in done) > min(r.t_enqueue for r in done)
    assert ledger.bill("serve").invocations > 0
    inv.deallocate()


def test_session_residency_is_server_side():
    """The KV cache never travels: decode payload is just (sid, token)."""
    cfg, server, inv, _ = make_llm_stack()
    toks = np.ones((2, 4), np.int32)
    out = inv.invoke("prefill", {"tokens": toks})
    sid = out["sid"]
    assert sid in server._sessions
    f = inv.submit("decode",
                   {"sid": sid, "tokens": out["next_token"][:, None]})
    res = f.get()
    # wire bytes for the decode invocation ~ tokens only (< 1 KiB),
    # cache itself is orders of magnitude larger
    assert f.invocation.bytes_in < 1024
    assert res["next_token"].shape == (2,)
    inv.invoke("close_session", {"sid": sid})
    assert sid not in server._sessions
    inv.deallocate()


def test_generation_greedy_deterministic():
    cfg, server, inv, _ = make_llm_stack()
    engine1 = ServeEngine(inv, batch_size=1)
    r1 = engine1.enqueue(np.arange(1, 6), max_new_tokens=5)
    engine1.run()
    engine2 = ServeEngine(inv, batch_size=1)
    r2 = engine2.enqueue(np.arange(1, 6), max_new_tokens=5)
    engine2.run()
    assert r1.tokens_out == r2.tokens_out      # greedy + same params
    inv.deallocate()


def test_backup_submit_straggler():
    from repro.core import FunctionLibrary
    import time as _t
    lib = FunctionLibrary("slow")
    calls = {"n": 0}

    def maybe_slow(x):
        calls["n"] += 1
        if calls["n"] == 1:
            _t.sleep(0.2)                       # straggler
        return x * 2

    lib.register("f", maybe_slow)
    ledger = Ledger()
    rm = ResourceManager(n_replicas=1)
    bs = BatchSystem(rm, ledger, n_nodes=1, workers_per_node=2)
    bs.release_idle()
    inv = Invoker("c", rm, lib, seed=0)
    inv.allocate(2)
    out, used_backup = backup_submit(inv, "f",
                                     np.ones(4, np.float32), 0.02)
    assert (out == 2.0).all()
    assert used_backup                          # the duplicate won
    inv.deallocate()


def test_serving_survives_worker_crash():
    cfg, server, inv, _ = make_llm_stack(fault_rate=0.0)
    # crash the worker currently holding the connection mid-stream;
    # the wave engine's next invocation retries on another worker
    engine = ServeEngine(inv, batch_size=2)
    rng = np.random.default_rng(1)
    for _ in range(3):
        engine.enqueue(rng.integers(1, cfg.vocab_size, size=4),
                       max_new_tokens=3)
    # pre-allocate a second worker so retry has a target
    inv.allocate(1)
    done = engine.run()
    assert len(done) == 3
    inv.deallocate()


def test_failed_decode_keeps_session_and_cause():
    """A decode step that raises leaves its session in place, so the
    client's retries meet the same error (not a missing session), and the
    original error reaches the client as the cause of ExecutorCrash."""
    cfg, server, inv, _ = make_llm_stack()
    out = inv.invoke("prefill", {"tokens": np.ones((2, 4), np.int32)})
    sid, nxt = out["sid"], out["next_token"][:, None]
    step = server._decode_fn

    def lost_device(*args):
        raise RuntimeError("device lost")

    server._decode_fn = lost_device
    with pytest.raises(ExecutorCrash) as info:
        inv.invoke("decode", {"sid": sid, "tokens": nxt})
    assert isinstance(info.value.__cause__, RuntimeError)
    assert "device lost" in str(info.value.__cause__)
    assert sid in server._sessions
    server._decode_fn = step
    res = inv.invoke("decode", {"sid": sid, "tokens": nxt})
    assert res["next_token"].shape == (2,)
    inv.deallocate()


class _FailedOnDevice:
    """Stands for an output buffer of a step that failed while it ran:
    reading it, or passing it to the next step, raises the device's
    error."""

    def __getitem__(self, _):
        raise RuntimeError("device lost")


def test_decode_failing_on_device_reports_device_error():
    """A step that dispatches but fails on the device raises at the host
    read, after the donated cache was replaced by the step's outputs.
    The retries then meet the same device error, never a KeyError."""
    cfg, server, inv, _ = make_llm_stack()
    out = inv.invoke("prefill", {"tokens": np.ones((2, 4), np.int32)})
    sid, nxt = out["sid"], out["next_token"][:, None]
    calls = []

    def failing_step(params, cache, tokens, length):
        calls.append(cache)
        if isinstance(cache, _FailedOnDevice):
            raise RuntimeError("device lost")
        return _FailedOnDevice(), _FailedOnDevice(), length

    server._decode_fn = failing_step
    with pytest.raises(ExecutorCrash) as info:
        inv.invoke("decode", {"sid": sid, "tokens": nxt})
    assert len(calls) > 1, "the client did not retry"
    assert isinstance(info.value.__cause__, RuntimeError)
    assert "device lost" in str(info.value.__cause__)
    assert sid in server._sessions
    inv.deallocate()


def test_serve_launcher_smoke():
    """The launcher's path at a reduced width: warm-up wave, timed waves,
    every token in the vocabulary."""
    from repro.launch.serve import serve
    cfg = get_smoke("h2o-danube-3-4b")
    run = serve(cfg, n_requests=4, batch=2, prompt_len=8, new_tokens=3,
                max_len=32)
    assert [len(r.tokens_out) for r in run.requests] == [3] * 4
    assert all(0 <= t < cfg.vocab_size
               for r in run.requests for t in r.tokens_out)
    assert run.tokens == 12 and run.compile_s > 0
    assert run.bill_invocations > 0
    assert all(r.ttft <= r.latency for r in run.requests)
    assert any(line.startswith("request 4:") for line in run.lines())
    with pytest.raises(ValueError):
        serve(cfg, n_requests=3, batch=2, prompt_len=8, max_len=32)


def test_compile_cache_left_to_environment(monkeypatch, tmp_path):
    from repro.launch.serve import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_chip_smoke_refuses_cpu():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=root,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
