"""Model FLOP utilization of the whole decode step as the executor runs
it: the model operations of the step's rows (``flops.token_flops`` at the
position each row writes) over the step's executor-side wall time
(``exec.decode``: dispatch, the device programs and the host read of the
token) times the chips' peak, over every decode step of the window.  It
bounds ``decode_hbm_roofline``, which reads only the device program: a
change that takes that program off the path leaves the roofline silent,
and this still reads."""
from chipbench import flops
from chipbench.metrics._common import window_spans


def read(ctx):
    steps = [s for s in window_spans(ctx, "exec.decode") if "rows" in s.meta]
    if not steps:
        return None
    work = sum(s.meta["rows"] * flops.token_flops(ctx.config,
                                                  s.meta["ctx"] - 1)
               for s in steps)
    wall = sum(s.dur for s in steps)
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips
    return 100.0 * work / (wall * peak)
