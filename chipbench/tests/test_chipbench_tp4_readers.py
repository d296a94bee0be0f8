"""The tensor-parallel cell's readers on hand-built four-chip traces:
the decode program's collective share counts the operations its
compiled HLO names collectives (a fusion among them) and no others, the
HBM roofline divides by every chip's bandwidth, and a trace without the
compiled programs reads None."""
import gzip
import importlib
import importlib.util
import os
from types import SimpleNamespace

import pytest

from chipbench import HERE, flops, harness, spans as S, trace_reduce
from chipbench.trace_reduce import Device, TraceSummary

CONFIG = harness.load_json(os.path.join(HERE, "configs",
                                        "mistral-nemo-12b-tp4.json"))
PEAKS = harness.peaks_for("TPU v5 lite")
DECODE = "jit_decode(42)"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "tp4_reader", os.path.join(HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def chip(i, op_ns, modules=()):
    return Device(f"/device:TPU:{i}", [(0, 10)], dict(op_ns), list(modules))


def test_collective_share_counts_the_named_ops_fused_or_not():
    share = reader("tp4.decode_collective_share").share
    ops = {f"{DECODE}:%fusion.138": 30,          # holds the all-gather
           f"{DECODE}:%all-reduce.11": 10,
           f"{DECODE}:%fusion.7": 60,            # a fusion with none
           "jit__lambda(9):%all-reduce.3": 500}  # the prefill's: not counted
    trace = TraceSummary((0, 10), [chip(i, ops) for i in range(4)])
    sets = {DECODE: {"%fusion.138", "%all-reduce.11"}}
    assert share(trace, sets) == pytest.approx(40.0)
    # averaged over the chips, each chip's own share
    other = dict(ops, **{f"{DECODE}:%fusion.7": 160})
    trace = TraceSummary((0, 10), [chip(0, ops), chip(1, other),
                                   chip(2, ops), chip(3, other)])
    assert share(trace, sets) == pytest.approx((40.0 + 20.0) / 2)


def test_collective_share_without_the_compiled_programs_reads_none(
        tmp_path, monkeypatch):
    mod = reader("tp4.decode_collective_share")
    trace = TraceSummary((0, 10), [chip(0, {f"{DECODE}:%fusion.138": 5})])
    assert mod.share(trace, {}) is None
    assert mod.read(SimpleNamespace(trace=None, workload="x")) is None
    # a traced run whose trace is not where the harness writes it
    monkeypatch.setattr(mod, "HERE", str(tmp_path))
    ctx = SimpleNamespace(trace=trace, workload="nemo-12b-tp4.chat-poisson")
    with pytest.raises(FileNotFoundError):
        mod.read(ctx)


def test_collective_share_reads_the_trace_of_a_traced_run(tmp_path,
                                                          monkeypatch):
    """End to end on the recorded one-chip v5e trace, where the harness
    leaves it: its decode programs' modules are found and hold no
    collective, so the share reads 0."""
    mod = reader("tp4.decode_collective_share")
    run = tmp_path / "out" / "trace" / "nemo-12b-tp4.chat-poisson" / "run"
    run.mkdir(parents=True)
    with gzip.open(os.path.join(HERE, "testdata",
                                "smoke_v5e.xplane.pb.gz")) as f:
        (run / "smoke.xplane.pb").write_bytes(f.read())
    monkeypatch.setattr(mod, "HERE", str(tmp_path))
    trace = trace_reduce.reduce(str(run / "smoke.xplane.pb"))
    ctx = SimpleNamespace(trace=trace, workload="nemo-12b-tp4.chat-poisson")
    assert mod.read(ctx) == 0.0


def roofline_ctx(chips, rows=1, ctx_len=1500, steps=3):
    least = flops.decode_least_bytes(CONFIG, rows, ctx_len)
    ns = int(round(least / (chips * PEAKS["hbm_bytes_per_s"]) * 1e9))
    mods = [(DECODE, 1000 * k, ns) for k in range(steps)]
    trace = TraceSummary((0, 10 ** 12),
                         [chip(i, {}, mods) for i in range(chips)])
    spans = S.Spans()
    spans.items = [S.Span("exec.decode", k, k + 0.5,
                          {"rows": rows, "ctx": ctx_len})
                   for k in range(steps)]
    return SimpleNamespace(trace=trace, spans=spans, window_start=0.0,
                           run_end=float(steps), config=CONFIG, peaks=PEAKS,
                           chips=chips, workload="nemo-12b-tp4.chat-poisson")


def test_hbm_roofline_divides_by_every_chips_bandwidth():
    ctx = roofline_ctx(chips=4)
    assert harness.read_metric("tp4.decode_hbm_roofline", ctx) == \
        pytest.approx(100.0, rel=1e-6)
    assert harness.read_metric("decode_hbm_roofline", ctx) == \
        pytest.approx(400.0, rel=1e-6)


@pytest.mark.parametrize("name", ["step_mfu", "device_idle_share",
                                  "decode_host_us"])
def test_the_other_readers_are_the_one_chip_formulas(name):
    one_chip = importlib.import_module("chipbench.metrics." + name)
    assert reader("tp4." + name).read is one_chip.read
