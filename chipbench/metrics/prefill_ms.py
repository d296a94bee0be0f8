"""Median executor-side wall time of a wave's prefill (``exec.prefill``),
which ends with the host read of the first token."""
from chipbench.metrics._common import median, window_spans


def read(ctx):
    v = median([s.dur for s in window_spans(ctx, "exec.prefill")])
    return None if v is None else v * 1e3
