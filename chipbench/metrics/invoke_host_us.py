"""Median over decode invocations of the platform's own host work on
both sides: the client's ``invoke.submit`` (record minted, payload
walked, modelled send, on the executor's queue) plus the executor's
``exec.return`` (wait for the result, modelled return, accounting,
future fulfilled), each the one inside the client's ``invoke.decode``.
Invocations without exactly one of each are left out; None where none
has them."""
from chipbench.metrics import _host_spans as H
from chipbench.metrics._common import median


def read(ctx):
    calls = H.spans(ctx, "invoke.decode")
    subs = H.inside(calls, H.spans(ctx, "invoke.submit"))
    rets = H.inside(calls, H.spans(ctx, "exec.return"))
    v = median([H.dur(s) + H.dur(r) for s, r in zip(subs, rets)
                if s is not None and r is not None])
    return None if v is None else v * 1e-3
