"""Share of the HBM roofline the decode program reaches over all the
cell's chips: the least bytes of a step (``flops.decode_least_bytes``, the
same work whatever layout implements it) over the chips' summed HBM
bandwidth, divided by the decode program's device time, both averaged
over the decode steps of the traced window.  It is ``decode_hbm_roofline``
divided by the chips, which that one-chip formula leaves out: bytes a
layout moves twice (weights replicated on every chip) show as lost
share."""
from chipbench.metrics import decode_hbm_roofline


def read(ctx):
    one_chip = decode_hbm_roofline.read(ctx)
    return None if one_chip is None else one_chip / ctx.chips
