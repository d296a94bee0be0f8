"""Median over the window's decode steps of the executor's host time not
spent waiting on the device: the ``exec.decode`` span less the program's
``exec.decode.read`` inside it (the token's copy to the device, the
step's dispatch, the sampling programs put behind it, the session's
bookkeeping).  Steps without exactly one ``.read`` are left out; None
where no step has one."""
from chipbench.metrics import _host_spans as H
from chipbench.metrics._common import median


def read(ctx):
    steps = H.spans(ctx, "exec.decode")
    reads = H.inside(steps, H.spans(ctx, "exec.decode.read"))
    v = median([H.dur(s) - H.dur(r) for s, r in zip(steps, reads)
                if r is not None])
    return None if v is None else v * 1e-3
