"""Median over decode invocations of the client-side ``invoke.decode``
wall time minus the executor-side ``exec.decode`` wall time: what the
invoker, the lease's channel and the executor's queue add to a step."""
from chipbench.metrics._common import median, window_spans


def read(ctx):
    inv = window_spans(ctx, "invoke.decode")
    exe = window_spans(ctx, "exec.decode")
    if not inv or len(inv) != len(exe):
        return None
    v = median([a.dur - b.dur for a, b in zip(inv, exe)])
    return v * 1e6
