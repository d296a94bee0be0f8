"""Serving engine: model steps as rFaaS functions (DESIGN.md §3).

``ModelServer`` is the executor-side state: compiled prefill/decode steps
plus per-session KV caches that stay RESIDENT between invocations — the
TPU-native reading of the paper's hot invocations (the Jacobi use-case's
"cache the system matrix in the warm sandbox" is exactly KV residency:
the client ships only the new tokens, never the cache).  Donated cache
buffers make the decode step zero-copy on the executor.

``ServeEngine`` is the client: it leases workers through the Invoker,
pushes the model function library, and drives wave-scheduled batched
generation, stamping each request's enqueue, first token and end on its
clock; ``backup_submit`` adds straggler backup requests for stateless
functions.
"""
from __future__ import annotations

import itertools
import threading
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import FunctionLibrary, Invoker
from repro.core.clock import Clock
from repro.core.tracing import span

_session_ids = itertools.count(1)


class ModelServer:
    """Executor-side function bundle for one model."""

    def __init__(self, model, params, *, max_len: int = 256,
                 jit_steps: bool = True):
        self.model = model
        self.params = params
        self.max_len = max_len
        self._sessions: Dict[int, tuple] = {}       # sid -> (cache, length)
        self._lock = threading.Lock()
        if hasattr(model, "cache_device"):
            # the cache lives where the weights do (on a mesh, any of its
            # devices, which are alike)
            model.cache_device = next(iter(
                jax.tree.leaves(params)[0].sharding.device_set))
        if jit_steps:
            self._prefill_fn = jax.jit(
                lambda p, t: model.prefill(p, t, self.max_len))
            self._decode_fn = jax.jit(model.decode, donate_argnums=(1,))
        else:
            self._prefill_fn = lambda p, t: model.prefill(p, t,
                                                          self.max_len)
            self._decode_fn = model.decode

    # ------------------------------------------------- executor functions
    # Each step is four spans on the executor's thread (core/tracing.py):
    # ``.input`` the tokens' copy to the device, ``.dispatch`` the jitted
    # step's call (it returns before the device is done), ``.sample`` the
    # slice and argmax put behind it, ``.read`` the wait for the device
    # and the copy of the next token to the host.
    def prefill(self, payload: dict) -> dict:
        """payload: {"tokens": (b, s) int}.  Creates a resident session;
        the cache NEVER travels back to the client (zero-copy residency)."""
        sid = next(_session_ids)
        with span("exec.prefill.input", sid=sid,
                  rows=len(payload["tokens"])):
            tokens = jnp.asarray(payload["tokens"])
        with span("exec.prefill.dispatch", sid=sid):
            logits, cache, length = self._prefill_fn(self.params, tokens)
        with self._lock:
            self._sessions[sid] = (cache, length)
        with span("exec.prefill.sample", sid=sid):
            next_tok = jnp.argmax(logits[:, -1], axis=-1)
        with span("exec.prefill.read", sid=sid):
            next_tok = np.asarray(next_tok, np.int32)
        return {"sid": sid, "next_token": next_tok}

    def decode(self, payload: dict) -> dict:
        """payload: {"sid": int, "tokens": (b, 1) int} -> next token.
        Hot path: compiled step + donated resident cache."""
        sid = int(payload["sid"])
        with self._lock:
            cache, length = self._sessions.pop(sid)
        with span("exec.decode.input", sid=sid,
                  rows=len(payload["tokens"])):
            tokens = jnp.asarray(payload["tokens"])
        try:
            with span("exec.decode.dispatch", sid=sid):
                logits, new_cache, length = self._decode_fn(
                    self.params, cache, tokens, length)
        except BaseException:
            # a step that fails to trace, compile or dispatch has not yet
            # consumed the donated cache: put the session back, so the
            # client's retry meets the same error, not a missing session.
            # A failure while the step runs on the device surfaces at the
            # host read below; the session then holds the step's outputs,
            # which carry that error into the retry.
            with self._lock:
                self._sessions[sid] = (cache, length)
            raise
        with self._lock:
            self._sessions[sid] = (new_cache, length)
        with span("exec.decode.sample", sid=sid):
            next_tok = jnp.argmax(logits[:, -1], axis=-1)
        with span("exec.decode.read", sid=sid):
            next_tok = np.asarray(next_tok, np.int32)
        return {"sid": sid, "next_token": next_tok}

    def close_session(self, payload: dict) -> dict:
        with self._lock:
            self._sessions.pop(int(payload["sid"]), None)
        return {"ok": True}

    def make_library(self, name: str = "llm") -> FunctionLibrary:
        lib = FunctionLibrary(name, code_size=1 << 20)
        lib.register("prefill", self.prefill)
        lib.register("decode", self.decode)
        lib.register("close_session", self.close_session)
        return lib


@dataclass
class GenRequest:
    prompt: np.ndarray                       # (s,) int32
    max_new_tokens: int = 16
    request_id: int = 0
    t_enqueue: float = 0.0
    tokens_out: List[int] = field(default_factory=list)
    t_first_token: Optional[float] = None
    t_done: Optional[float] = None

    @property
    def ttft(self) -> Optional[float]:
        return (None if self.t_first_token is None
                else self.t_first_token - self.t_enqueue)

    @property
    def latency(self) -> Optional[float]:
        return None if self.t_done is None else self.t_done - self.t_enqueue


class ServeEngine:
    """Client-side wave-batched generation over leased rFaaS workers."""

    def __init__(self, invoker: Invoker, *, batch_size: int = 4,
                 eos_token: int = -1, clock: Optional[Clock] = None):
        self.invoker = invoker
        self.batch_size = batch_size
        self.eos_token = eos_token
        # default to the invoker's clock: request timestamps must live
        # on the same timeline the invocations complete on
        self.clock = invoker.clock if clock is None else clock
        # enqueue() may run on another thread than run(): deque's append
        # and popleft are atomic, and run() is the queue's one consumer
        self._queue: Deque[GenRequest] = deque()
        self._rid = itertools.count(1)
        self.completed: List[GenRequest] = []

    def enqueue(self, prompt, max_new_tokens: int = 16) -> GenRequest:
        req = GenRequest(np.asarray(prompt, np.int32), max_new_tokens,
                         next(self._rid), self.clock.now())
        self._queue.append(req)
        return req

    def run(self) -> List[GenRequest]:
        """Drain the queue in waves of ``batch_size``.  A request enqueued
        by another thread meanwhile joins the next wave."""
        q = self._queue
        while q:
            self._run_wave([q.popleft()
                            for _ in range(min(self.batch_size, len(q)))])
        return self.completed

    def _run_wave(self, wave: List[GenRequest]):
        # left-pad prompts to a common length with token 0
        s = max(len(r.prompt) for r in wave)
        toks = np.zeros((len(wave), s), np.int32)
        for i, r in enumerate(wave):
            toks[i, s - len(r.prompt):] = r.prompt
        out = self.invoker.invoke("prefill", {"tokens": toks})
        sid = out["sid"]
        nxt = out["next_token"]
        now = self.clock.now()
        for i, r in enumerate(wave):
            r.tokens_out.append(int(nxt[i]))
            r.t_first_token = now
        max_new = max(r.max_new_tokens for r in wave)
        for step in range(1, max_new):
            out = self.invoker.invoke(
                "decode", {"sid": sid, "tokens": nxt[:, None]})
            nxt = out["next_token"]
            now = self.clock.now()
            for i, r in enumerate(wave):
                if len(r.tokens_out) < r.max_new_tokens and \
                        (not r.tokens_out
                         or r.tokens_out[-1] != self.eos_token):
                    r.tokens_out.append(int(nxt[i]))
                    if len(r.tokens_out) >= r.max_new_tokens:
                        r.t_done = now
        now = self.clock.now()
        for r in wave:
            if r.t_done is None:
                r.t_done = now
        self.invoker.invoke("close_session", {"sid": sid})
        self.completed.extend(wave)


def backup_submit(invoker: Invoker, fn_name: str, payload,
                  deadline_s: float, clock: Optional[Clock] = None):
    """Straggler mitigation for STATELESS functions: duplicate dispatch
    after a deadline, first result wins (DESIGN.md §9).  Deadline
    polling runs on the invoker's clock (overridable), so simulated
    deadlines neither sleep nor drift."""
    clock = invoker.clock if clock is None else clock
    f1 = invoker.submit(fn_name, payload)
    t0 = clock.now()
    while not f1.done() and clock.now() - t0 < deadline_s:
        clock.sleep(deadline_s / 50)
    if f1.done():
        return f1.get(0.0), False
    f2 = invoker.submit(fn_name, payload)          # backup request
    while True:
        if f1.done():
            return f1.get(0.0), False
        if f2.done():
            return f2.get(0.0), True
        clock.sleep(deadline_s / 50)
