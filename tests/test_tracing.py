"""The program's own spans (``repro.core.tracing``) in the profiler's
trace: where the served path writes them, that the simulator writes
none, and the model scopes that name the device's operations."""
from __future__ import annotations

import glob
import re
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core import (BatchSystem, Invoker, Ledger, ResourceManager,
                        SimulatedCluster)
from repro.core.tracing import span
from repro.models.factory import build_model
from repro.serving import ModelServer, ServeEngine

SERVED = ("invoke.submit", "invoke.wait", "exec.return")
STEP = ("input", "dispatch", "sample", "read")
PROGRAM_SPANS = SERVED + tuple(f"exec.{s}.{p}" for s in ("prefill", "decode")
                               for p in STEP) + ("rm.heartbeat_sweep",)


def host_events(trace_dir):
    """(name, start_ns, end_ns, thread line, stats) of every event on the
    trace's host plane."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for li, line in enumerate(plane.lines):
                for ev in line.events:
                    out.append((ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns), li,
                                dict(ev.stats)))
    return out


def traced(trace_dir, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return host_events(trace_dir)


class OuterSpans:
    """The invoker as a client sees it, each call marked ``invoke.<fn>``
    from outside, as a benchmark would."""

    def __init__(self, invoker):
        self._invoker = invoker
        self.clock = invoker.clock

    def invoke(self, fn_name, payload, timeout=60.0):
        with span("invoke." + fn_name):
            return self._invoker.invoke(fn_name, payload, timeout)


@pytest.fixture(scope="module")
def served_trace(tmp_path_factory):
    cfg = get_smoke("h2o-danube-3-4b")
    model = build_model(cfg)
    server = ModelServer(model, model.init(jax.random.PRNGKey(0)),
                         max_len=32)
    rm = ResourceManager(n_replicas=1)
    BatchSystem(rm, Ledger(), n_nodes=1, workers_per_node=1).release_idle()
    inv = Invoker("trace", rm, server.make_library(), seed=0)
    inv.allocate(1)
    engine = ServeEngine(OuterSpans(inv), batch_size=2)
    rng = np.random.default_rng(0)
    engine.enqueue(rng.integers(1, cfg.vocab_size, 6), max_new_tokens=4)
    engine.run()                         # compile outside the trace
    for _ in range(2):
        engine.enqueue(rng.integers(1, cfg.vocab_size, 6), max_new_tokens=4)

    def serve():
        rm.start_heartbeats(interval_s=0.01)
        engine.run()
        time.sleep(0.05)                 # a sweep or more
        rm.stop()
    try:
        events = traced(tmp_path_factory.mktemp("served"), serve)
    finally:
        rm.stop()
        inv.deallocate()
    return events


def within(events, name, lo, hi):
    return [e for e in events if e[0] == name and lo <= e[1] and e[2] <= hi]


@pytest.mark.parametrize("fn", ["prefill", "decode"])
def test_each_invocation_holds_its_spans_nested(served_trace, fn):
    calls = [e for e in served_trace if e[0] == "invoke." + fn]
    assert len(calls) == (1 if fn == "prefill" else 3)
    for _, lo, hi, client, _ in calls:
        got = {n: within(served_trace, n, lo, hi)
               for n in SERVED + tuple(f"exec.{fn}.{p}" for p in STEP)}
        assert all(len(v) == 1 for v in got.values()), \
            {n: len(v) for n, v in got.items()}
        (sub,), (wait,), (ret,) = (got[n] for n in SERVED)
        # the client's thread: submit, then the wait, inside the call
        assert sub[3] == wait[3] == client
        assert sub[2] <= wait[1]
        # the executor's thread: the step's four spans in order, then the
        # return, all before the client's wait ends
        steps = [got[f"exec.{fn}.{p}"][0] for p in STEP] + [ret]
        assert {e[3] for e in steps} == {ret[3]} != {client}
        assert all(a[2] <= b[1] for a, b in zip(steps, steps[1:]))
        assert sub[1] <= steps[0][1] and ret[2] <= wait[2]
        # metadata rides as stats, never in the name
        inv_id = sub[4]["inv"]
        assert sub[4]["fn"] == fn
        assert wait[4]["inv"] == ret[4]["inv"] == inv_id
        assert got[f"exec.{fn}.input"][0][4]["rows"] == 2
        sids = {got[f"exec.{fn}.{p}"][0][4]["sid"] for p in STEP}
        assert len(sids) == 1


def test_close_session_and_heartbeat_spans(served_trace):
    names = {e[0] for e in served_trace}
    assert "rm.heartbeat_sweep" in names
    assert {n for n in names if n.startswith(("invoke.", "exec."))} <= \
        set(PROGRAM_SPANS) | {"invoke.prefill", "invoke.decode",
                              "invoke.close_session"}
    closes = [e for e in served_trace if e[0] == "invoke.close_session"]
    assert len(closes) == 1
    _, lo, hi, _, _ = closes[0]
    assert [len(within(served_trace, n, lo, hi)) for n in SERVED] == [1] * 3


def test_virtual_clock_replay_writes_no_spans(tmp_path):
    def replay():
        sim = SimulatedCluster(n_nodes=2, workers_per_node=2, seed=3)
        stats = sim.run_multi_tenant(n_clients=2, n_invocations=200,
                                     lease_timeout_s=0.05)
        assert stats.completed > 0
    events = traced(tmp_path, replay)
    assert not [e for e in events if e[0] in PROGRAM_SPANS]


def test_decode_operations_carry_the_model_scopes():
    cfg = get_smoke("h2o-danube-3-4b")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(2, 32))
    low = jax.jit(model.decode, donate_argnums=(1,)).lower(
        params, cache, jax.ShapeDtypeStruct((2, 1), np.int32),
        jax.ShapeDtypeStruct((), np.int32))
    names = re.findall(r'op_name="([^"]+)"', low.compile().as_text())
    for scope in ("attention", "mlp", "head"):
        assert any(f"/{scope}/" in n or n.startswith(f"{scope}/")
                   for n in names), scope
    # the jit keeps the name the benchmark's trace reduction finds it by
    assert low.compile().as_text().startswith("HloModule jit_decode")


class InstantInvoker:
    """Answers every step at once with token 1: the client's queue
    handling alone is exercised."""

    def __init__(self, clock):
        self.clock = clock

    def invoke(self, fn_name, payload, timeout=None):
        if fn_name == "close_session":
            return {"ok": True}
        return {"sid": 1,
                "next_token": np.ones(len(payload["tokens"]), np.int32)}


def test_enqueue_from_another_thread_during_run():
    """A second thread enqueues while ``run()`` drains waves: no request
    is lost, and each is served exactly once."""
    from repro.core import REAL_CLOCK
    engine = ServeEngine(InstantInvoker(REAL_CLOCK), batch_size=3)
    n = 20000
    started = threading.Event()

    def produce():
        started.set()
        for i in range(n):
            engine.enqueue(np.array([i + 1]), max_new_tokens=2)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        engine.enqueue(np.array([0]), max_new_tokens=2)
        t = threading.Thread(target=produce)
        t.start()
        started.wait(10)
        while t.is_alive():
            engine.run()
        t.join(10)
        assert not t.is_alive()
        engine.run()
    finally:
        sys.setswitchinterval(interval)
    done = engine.completed
    assert len(done) == n + 1
    assert sorted(int(r.prompt[0]) for r in done) == list(range(n + 1))
    assert all(r.tokens_out == [1, 1] for r in done)
