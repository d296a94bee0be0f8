"""The reduction from a profiler trace to device time, on a small trace
recorded on a TPU v5e: the smoke-size cell, a 2-second window, traced by
``run.py``'s own path (``testdata/smoke_v5e.xplane.pb.gz``)."""
import gzip
import os

import pytest

from chipbench import HERE, trace_reduce as R

TRACE = os.path.join(HERE, "testdata", "smoke_v5e.xplane.pb.gz")
# what the reduction read from this trace when it was recorded (the
# harness's "window" span, and the union of the chip's operation events
# inside it); a change to the reduction that moves them says why
WINDOW_S = 2.0175554470000003
BUSY_S = 0.001072012


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "smoke.xplane.pb"
    with gzip.open(TRACE) as f:
        path.write_bytes(f.read())
    return R.reduce(str(path))


def test_window_and_busy_time(summary):
    assert len(summary.devices) == 1
    assert summary.devices[0].name == "/device:TPU:0"
    assert 0 < summary.busy_s < summary.window_s
    assert summary.window_s == pytest.approx(WINDOW_S, rel=1e-9)
    assert summary.busy_s == pytest.approx(BUSY_S, rel=1e-9)


def test_idle_gaps_cover_the_idle_time(summary):
    gaps = summary.idle_gaps(n=100)
    idle = summary.window_s - summary.busy_s
    assert sum(s for _, s in gaps) == pytest.approx(idle, rel=1e-6)
    names = [n for n, _ in gaps]
    assert names[0] == "wait.arrival"
    assert set(names) <= {"wait.arrival", "exec.decode", "exec.prefill",
                          "exec.close_session", "invoke.decode",
                          "invoke.prefill", "invoke.close_session", "none"}


def test_each_program_run_matches_a_host_call(summary):
    for program, span in (("decode", "exec.decode"),
                          ("prefill", "exec.prefill")):
        calls = [h for h in summary.host_spans if h[0] == span
                 and summary.window[0] <= h[1] < summary.window[1]]
        assert len(summary.program_ns(program)) == len(calls) > 0


def test_top_ops_are_self_times_by_program(summary):
    top = summary.top_ops()
    assert 0 < len(top) <= 10
    assert all(name.startswith("jit_") and ":%" in name for name, _ in top)
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    total = sum(summary.devices[0].op_ns.values()) * 1e-9
    assert total <= summary.busy_s * (1 + 1e-9)


def test_interval_arithmetic():
    assert R.merge([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    assert R.gaps([(2, 4), (6, 7)], (0, 10)) == [(0, 2), (4, 6), (7, 10)]
    assert R.clip([(0, 5), (8, 12)], (3, 10)) == [(3, 5), (8, 10)]
    ops = [(0, 100, "%w = a"), (10, 40, "%x = b"), (120, 130, "%x = b")]
    assert R.self_times(ops, [("jit_f(1)", 0, 200)]) == \
        {"jit_f(1):%w": 70, "jit_f(1):%x": 40}
