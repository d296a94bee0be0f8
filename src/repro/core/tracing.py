"""Spans of the served path, written into the JAX profiler's trace.

Each span is a ``jax.profiler.TraceAnnotation``: when a profiler is
running (``jax.profiler.start_trace``, or xprof attached to the process)
it lands on the ``/host:CPU`` plane of the same trace as the device's
operations, on that trace's clock; when none is, it costs about a
microsecond.  The profiler is the only recorder: nothing is kept in
memory and there is nothing to switch on.  Metadata passed as keywords
arrives as the event's stats; the event's name stays exactly as written.

This module is the program's only user of the profiler API.  ``jax`` is
imported on the first span, so a session that never opens one (the
virtual-clock simulator) never imports XLA.

Span names of the served path, by thread:

- client: ``invoke.submit`` (record minted -> on the executor's queue),
  ``invoke.wait`` (blocked on the result, retries included);
- executor: ``exec.return`` (function returned -> future fulfilled),
  and inside ``ModelServer``'s steps ``exec.<step>.input``,
  ``.dispatch``, ``.sample`` and ``.read``;
- heartbeat sweeper: ``rm.heartbeat_sweep``.
"""
from __future__ import annotations

_annotation = None


def span(name: str, **meta):
    """A context manager marking ``name`` in the profiler's trace, with
    ``meta`` as its stats.  The object returned is the annotation itself:
    its ``set_metadata(**meta)`` adds stats known only once it is open."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation(name, **meta)
