"""Readers' helpers for the program's own spans in the profiler's trace
(``repro.core.tracing``): ``invoke.submit``, ``invoke.wait``,
``exec.return`` and ``exec.<step>.{input,dispatch,sample,read}``.

They sit in ``TraceSummary.host_spans`` beside the benchmark's spans
(``invoke.<fn>`` on the client's side, ``exec.<fn>`` on the
executor's), on the trace's one clock.  A program span belongs to the
benchmark span whose interval holds it: the served path runs one
invocation at a time, and each of its spans opens and closes inside
the client's ``invoke.<fn>``.  A program without these spans gives the
readers nothing to pair, and they read None."""
import bisect


def spans(ctx, name):
    """(start, end) in ns of each host span named ``name`` that starts
    inside the traced window, in order of start; none without a trace."""
    if ctx.trace is None:
        return []
    lo, hi = ctx.trace.window
    return sorted((s, e) for n, s, e in ctx.trace.host_spans
                  if n == name and lo <= s < hi)


def inside(outer, inner):
    """For each interval of ``outer``, the one interval of ``inner`` that
    lies within it, or None where there is none or more than one.  Both
    lists are in order of start."""
    starts = [s for s, _ in inner]
    out = []
    for s, e in outer:
        held = [c for c in inner[bisect.bisect_left(starts, s):
                                 bisect.bisect_right(starts, e)]
                if c[1] <= e]
        out.append(held[0] if len(held) == 1 else None)
    return out


def dur(interval):
    return interval[1] - interval[0]
