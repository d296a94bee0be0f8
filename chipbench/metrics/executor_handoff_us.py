"""Median over decode invocations of the two thread hand-offs: from the
end of the client's ``invoke.submit`` to the start of the executor's
``exec.decode`` (the queue to the executor's thread), plus from the end
of the executor's ``exec.return`` to the end of the client's
``invoke.wait`` (the future back to the client), each span the one
inside the client's ``invoke.decode``.  Invocations without exactly one
of each are left out; None where none has them."""
from chipbench.metrics import _host_spans as H
from chipbench.metrics._common import median


def read(ctx):
    calls = H.spans(ctx, "invoke.decode")
    parts = [H.inside(calls, H.spans(ctx, name)) for name in
             ("invoke.submit", "exec.decode", "exec.return", "invoke.wait")]
    v = median([(x[0] - sub[1]) + (wait[1] - ret[1])
                for sub, x, ret, wait in zip(*parts)
                if None not in (sub, x, ret, wait)])
    return None if v is None else v * 1e-3
