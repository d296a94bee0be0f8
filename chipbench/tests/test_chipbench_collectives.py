"""``collectives``: the compiled modules a TPU trace holds, found by the
names its device lines give the programs, and joined with the
reduction's op names; on the recorded one-chip trace, whose decode
programs hold no collective."""
import gzip
import os

import pytest

from chipbench import HERE, collectives as C, trace_reduce as R

TRACE = os.path.join(HERE, "testdata", "smoke_v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def path(tmp_path_factory):
    p = tmp_path_factory.mktemp("trace") / "smoke.xplane.pb"
    with gzip.open(TRACE) as f:
        p.write_bytes(f.read())
    return str(p)


def test_every_decode_op_of_the_trace_is_an_instruction_of_its_module(path):
    modules = C.trace_modules(path, R.PROGRAMS["decode"])
    summary = R.reduce(path)
    runs = {name for name, _, _ in summary.devices[0].modules
            if name.startswith(R.PROGRAMS["decode"])}
    assert runs and runs <= set(modules)
    names = {prog: {"%" + i for _, instrs in C._computations(m).values()
                    for i, _, _ in instrs} for prog, m in modules.items()}
    ops = [key.partition(":") for key in summary.devices[0].op_ns]
    decode = [(prog, op) for prog, _, op in ops if prog in runs]
    assert decode and all(op in names[prog] for prog, op in decode)


def test_one_chip_programs_hold_no_collective(path):
    sets = C.in_trace(path, "jit_")
    assert len(sets) > 3 and not any(sets.values())


def test_a_fusion_holding_a_collective_is_one(path):
    """A hand-made module: the entry runs an all-reduce, a fusion whose
    computation holds an all-gather, and a fusion that holds none."""
    def field(num, payload):
        key = bytes([num << 3 | 2])
        return key + bytes([len(payload)]) + payload

    def varint_field(num, value):
        return bytes([num << 3, value])

    def instr(name, op, called=()):
        out = field(1, name.encode()) + field(2, op.encode())
        if called:
            out += b"\xb2\x02" + bytes([len(called)]) + bytes(called)
        return out

    def comp(cid, name, *instrs):
        return field(3, field(1, name.encode())
                     + b"".join(field(2, i) for i in instrs)
                     + varint_field(5, cid))

    module = (field(2, b"main")
              + comp(1, "fused_ag", instr("all-gather.1", "all-gather"))
              + comp(2, "fused_mul", instr("multiply.1", "multiply"))
              + comp(3, "main", instr("all-reduce.2", "all-reduce"),
                     instr("fusion.5", "fusion", [1]),
                     instr("fusion.6", "fusion", [2])))
    assert C.entry_computation(module) == "main"
    assert C.collective_ops(module) == [("main", "%all-reduce.2",
                                         "all-reduce"),
                                        ("main", "%fusion.5", "all-gather")]


def test_the_metadata_plane_is_read_alone_from_the_file(path):
    """The plane found by skipping the others in the file is the one a
    walk of the whole file in memory finds."""
    with open(path, "rb") as f:
        planes = [bytes(p) for num, p in C.fields(f.read()) if num == 1]
    whole = [p for p in planes if C._plane_name(p) == C.METADATA_PLANE]
    assert len(planes) > 1 and len(whole) == 1
    assert C._metadata_plane(path) == whole[0]
