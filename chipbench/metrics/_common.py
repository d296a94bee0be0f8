"""Helpers the per-layer metric readers share."""
import numpy as np


def window_spans(ctx, name):
    return [s for s in ctx.spans.items
            if s.name == name and ctx.window_start <= s.start <= ctx.run_end]


def median(values):
    return float(np.median(values)) if len(values) else None
