"""Spans recorded from the benchmark's own files, around calls into each
layer of the served path.

- ``TimedInvoker`` stands in for the ``Invoker`` that ``ServeEngine``
  calls, and times each ``invoke`` on the client's side
  (``invoke.prefill``, ``invoke.decode``, ``invoke.close_session``).
- ``time_library`` wraps every function of the program's own
  ``FunctionLibrary`` (``ModelServer.make_library``), which the lease
  pushes to the executor, and times each call on the executor's side
  (``exec.prefill``, ``exec.decode``, ...).  ``exec.decode`` includes the
  host read of the next token, which ``ModelServer.decode`` makes before
  it returns.
- The harness adds ``wait.arrival`` while it waits for the next request to
  fall due, and ``window`` around the measured window.

Every span is kept in memory on ``time.monotonic``, the clock
``ServeEngine`` stamps with.  With ``annotate`` each span is also written
as a ``jax.profiler.TraceAnnotation``, so that the profiler's trace can
attribute idle time on the device to what the host was doing.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional


@dataclass
class Span:
    name: str
    start: float
    end: float = 0.0
    meta: Dict[str, Any] = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.items: List[Span] = []          # appended by two threads

    @contextlib.contextmanager
    def span(self, name: str, **meta):
        s = Span(name, time.monotonic(), meta=meta)
        if self.annotate:
            import jax
            note = jax.profiler.TraceAnnotation(name)
        else:
            note = contextlib.nullcontext()
        with note:
            try:
                yield s
            finally:
                s.end = time.monotonic()
        self.items.append(s)


class TimedInvoker:
    """The ``Invoker`` as ``ServeEngine`` sees it, with each ``invoke``
    timed on the client's side.  Everything else is the invoker's own."""

    def __init__(self, invoker, spans: Spans):
        self._invoker = invoker
        self._spans = spans

    def invoke(self, fn_name: str, payload: Any,
               timeout: Optional[float] = 60.0) -> Any:
        with self._spans.span("invoke." + fn_name):
            return self._invoker.invoke(fn_name, payload, timeout)

    def __getattr__(self, name):
        return getattr(self._invoker, name)


def time_library(lib, spans: Spans) -> Callable[[], None]:
    """Wrap, in place, every function that ``lib`` registers, so that each
    call is an ``exec.<name>`` span.  ``exec.prefill`` and ``exec.decode``
    spans carry ``rows`` and ``ctx`` (the cache positions valid once the
    step has written its token), counted from the payloads and results
    alone.  Returns a function that drops the wrapped functions, and with
    them the ``ModelServer`` they are bound to."""
    fns = dict(lib._fns)
    ctx: Dict[int, List[int]] = {}           # sid -> [rows, positions]

    def meta(name: str, payload) -> Dict[str, Any]:
        if name == "prefill":
            rows, s = payload["tokens"].shape
            return {"rows": rows, "ctx": s}
        if name == "decode" and int(payload["sid"]) in ctx:
            rows, s = ctx[int(payload["sid"])]
            return {"rows": rows, "ctx": s + 1}
        return {}

    def wrap(name: str):
        def call(payload):
            m = meta(name, payload)
            with spans.span("exec." + name, **m):
                out = fns[name](payload)
            if name == "prefill":
                ctx[int(out["sid"])] = [m["rows"], m["ctx"]]
            elif m and name == "decode":
                ctx[int(payload["sid"])][1] = m["ctx"]
            elif name == "close_session":
                ctx.pop(int(payload["sid"]), None)
            return out
        return call

    for name in lib.symbols:
        lib._fns[name] = wrap(name)
    return fns.clear
