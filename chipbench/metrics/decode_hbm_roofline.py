"""Share of the HBM roofline the decode program reaches on the device:
the least bytes of a step (``flops.decode_least_bytes``: weights
multiplied by, the rows' embeddings, the keys and values each row attends
to) over the chip's HBM bandwidth, divided by the decode program's device
time, both averaged over the decode steps of the traced window."""
import numpy as np

from chipbench import flops
from chipbench.metrics._common import window_spans


def read(ctx):
    if ctx.trace is None:
        return None
    device = ctx.trace.program_ns("decode")
    steps = [s for s in window_spans(ctx, "exec.decode") if "rows" in s.meta]
    if not device or not steps:
        return None
    least = np.mean([flops.decode_least_bytes(ctx.config, s.meta["rows"],
                                              s.meta["ctx"]) for s in steps])
    least_s = least / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (np.mean(device) * 1e-9)
