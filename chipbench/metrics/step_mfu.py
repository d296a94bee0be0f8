"""Model FLOP utilization of the whole served path over the traced
window: the model operations of every prompt and output token the
window's requests had processed (``flops.token_flops``: 2 per weight
multiplied plus attention over the positions attended), over the window
times the chips times the chip's peak.  Rows a wave keeps computing after
their request has all its tokens are not counted."""
from chipbench import flops


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    total = 0.0
    for r in ctx.requests:
        if r.failed or not r.tokens:
            continue
        s = r.prompt_len
        total += flops.sequence_flops(ctx.config, 0, s)
        total += flops.sequence_flops(ctx.config, s, len(r.tokens) - 1)
    if total == 0:
        return None
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips
    return 100.0 * total / (ctx.trace.window_s * peak)
