"""Median executor-side wall time of a decode step (``exec.decode``),
including the host read of the next token."""
from chipbench.metrics._common import median, window_spans


def read(ctx):
    v = median([s.dur for s in window_spans(ctx, "exec.decode")])
    return None if v is None else v * 1e3
