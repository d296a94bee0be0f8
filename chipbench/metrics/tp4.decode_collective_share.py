"""Share of the decode program's device time spent in its collectives:
the self time of the collective operations inside ``jit_decode(...)``
runs over the self time of all operations of those runs, on each chip,
averaged over the chips.

Which operations are collectives is read from each decode program's
compiled HLO, which the TPU's trace holds (``chipbench/collectives.py``):
an all-reduce or all-gather, an async start or done, and a fusion whose
computation holds one, whatever the compiler named it.  A trace without
the compiled modules reads None; a traced run whose trace is not where
the harness writes it is an error."""
import os

from chipbench import HERE, collectives, trace_reduce

PROGRAM = trace_reduce.PROGRAMS["decode"]


def share(trace, sets):
    """Percent of decode op self time in the ops that ``sets`` (program
    name -> op names) lists, averaged over ``trace``'s chips; None
    without a set or without decode ops."""
    if not sets:
        return None
    shares = []
    for d in trace.devices:
        total = held = 0
        for key, ns in d.op_ns.items():
            prog, _, op = key.partition(":")
            if prog.startswith(PROGRAM):
                total += ns
                if op in sets.get(prog, ()):
                    held += ns
        if total:
            shares.append(held / total)
    return 100.0 * sum(shares) / len(shares) if shares else None


def read(ctx):
    if ctx.trace is None:
        return None
    # where the harness writes a traced run's trace; the context carries
    # the reduced trace alone
    where = os.path.join(HERE, "out", "trace", ctx.workload)
    path = trace_reduce.find(where)
    if path is None:
        raise FileNotFoundError(f"a traced run left no trace under {where}")
    return share(ctx.trace, collectives.in_trace(path, PROGRAM))
