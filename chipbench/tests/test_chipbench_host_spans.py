"""The readers of the program's own spans (``decode_host_us``,
``invoke_host_us``, ``executor_handoff_us``) on hand-built traces: spans
paired by time containment, invocations with a missing or doubled child
left out, and None from a program that writes no such spans."""
from types import SimpleNamespace

import pytest

from chipbench import harness
from chipbench.metrics import _host_spans as H
from chipbench.trace_reduce import TraceSummary

READERS = ("decode_host_us", "invoke_host_us", "executor_handoff_us")


def decode_call(t, read_ns=80_000, submit_ns=2_000, return_ns=1_500,
                drop=(), extra=()):
    """One decode invocation starting at ``t`` ns, as the trace holds it:
    the benchmark's ``invoke.decode`` and ``exec.decode``, and inside them
    the program's spans."""
    spans = {
        "invoke.decode": (t, t + 200_000),
        "invoke.submit": (t + 1_000, t + 1_000 + submit_ns),
        "invoke.wait": (t + 5_000, t + 199_000),
        "exec.decode": (t + 8_000, t + 100_000 + read_ns),
        "exec.decode.input": (t + 9_000, t + 10_000),
        "exec.decode.dispatch": (t + 10_000, t + 15_000),
        "exec.decode.sample": (t + 15_000, t + 16_000),
        "exec.decode.read": (t + 16_000, t + 16_000 + read_ns),
        "exec.return": (t + 190_000, t + 190_000 + return_ns),
    }
    out = [(n, s, e) for n, (s, e) in spans.items() if n not in drop]
    return out + [(n, *spans[n]) for n in extra]


def ctx_of(*calls, window=(0, 10_000_000)):
    spans = [("window", *window)] + [s for c in calls for s in c]
    return SimpleNamespace(trace=TraceSummary(window, [], spans))


def read(name, ctx):
    return harness.read_metric(name, ctx)


def test_each_reader_on_one_invocation():
    ctx = ctx_of(decode_call(1_000_000))
    # exec.decode 172 us less its read of 80 us
    assert read("decode_host_us", ctx) == pytest.approx(92.0)
    # submit 2 us + return 1.5 us
    assert read("invoke_host_us", ctx) == pytest.approx(3.5)
    # submit's end to exec.decode's start (5 us), return's end to the
    # wait's end (7.5 us)
    assert read("executor_handoff_us", ctx) == pytest.approx(12.5)


def test_medians_over_invocations_paired_by_containment():
    ctx = ctx_of(decode_call(1_000_000, read_ns=10_000, submit_ns=1_000),
                 decode_call(2_000_000, read_ns=20_000, submit_ns=3_000),
                 decode_call(3_000_000, read_ns=30_000, submit_ns=9_000))
    # the host time (92 us) does not depend on the read's length
    assert read("decode_host_us", ctx) == pytest.approx(92.0)
    assert read("invoke_host_us", ctx) == pytest.approx(3.0 + 1.5)
    # a longer submit shortens the first hand-off by as much
    assert read("executor_handoff_us", ctx) == pytest.approx(
        (7.0 - 3.0) + 7.5)


def test_an_invocation_missing_a_child_is_left_out():
    ctx = ctx_of(decode_call(1_000_000, drop=("exec.decode.read",
                                              "exec.return")),
                 decode_call(2_000_000, submit_ns=4_000))
    assert read("decode_host_us", ctx) == pytest.approx(92.0)
    assert read("invoke_host_us", ctx) == pytest.approx(5.5)
    assert read("executor_handoff_us", ctx) == pytest.approx(3.0 + 7.5)


@pytest.mark.parametrize("missing", ["exec.decode.read", "invoke.submit",
                                     "exec.return", "invoke.wait"])
def test_a_missing_child_everywhere_reads_none(missing):
    ctx = ctx_of(decode_call(1_000_000, drop=(missing,)),
                 decode_call(2_000_000, drop=(missing,)))
    values = {name: read(name, ctx) for name in READERS}
    needs = {"exec.decode.read": {"decode_host_us"},
             "invoke.submit": {"invoke_host_us", "executor_handoff_us"},
             "exec.return": {"invoke_host_us", "executor_handoff_us"},
             "invoke.wait": {"executor_handoff_us"}}[missing]
    assert {n for n, v in values.items() if v is None} == needs


def test_a_doubled_child_is_left_out():
    ctx = ctx_of(decode_call(1_000_000, extra=("exec.return",)))
    assert read("invoke_host_us", ctx) is None
    assert read("decode_host_us", ctx) == pytest.approx(92.0)


def test_a_program_without_its_own_spans_reads_none():
    """The benchmark's spans alone, as a program without spans of its own
    writes them."""
    only_outer = ("invoke.submit", "invoke.wait", "exec.return",
                  "exec.decode.input", "exec.decode.dispatch",
                  "exec.decode.sample", "exec.decode.read")
    ctx = ctx_of(decode_call(1_000_000, drop=only_outer))
    assert [read(name, ctx) for name in READERS] == [None] * 3
    assert [read(name, SimpleNamespace(trace=None)) for name in READERS] \
        == [None] * 3


def test_spans_outside_the_window_are_not_read():
    ctx = ctx_of(decode_call(1_000_000, read_ns=10_000),
                 decode_call(20_000_000), window=(0, 10_000_000))
    assert H.spans(ctx, "exec.decode") == [(1_008_000, 1_110_000)]
    assert read("decode_host_us", ctx) == pytest.approx(92.0)


def test_inside_pairs_each_outer_interval_with_its_one_child():
    outer = [(0, 10), (20, 30), (40, 50)]
    inner = [(1, 2), (21, 22), (23, 24), (45, 55)]
    assert H.inside(outer, inner) == [(1, 2), None, None]
