"""The collective operations of a compiled program, read from its HLO.

A collective is an instruction whose opcode moves data between chips
(``all-reduce``, ``all-gather``, ``reduce-scatter``, ``collective-permute``,
``all-to-all`` and their ``-start``/``-done`` halves), or an instruction
that runs a computation holding one: a fusion (the TPU compiler fuses an
all-gather with its neighbours, and splits it into fusions named
``async-collective-start``/``-done``) or an ``async-start``/``-done``.
Only instructions that run as operations of their own are listed: those
of the entry computation, of loop bodies and of branches, not those
inside a fusion.  A profiler trace names each operation by the same HLO
name, so the list joins with ``trace_reduce``'s op names as it stands,
with no pattern on names.

The HLO comes from the compiled program itself: ``Compiled.runtime_
executable().hlo_modules()`` before the program runs, or, after a run on
the TPU, the trace's ``/host:metadata`` plane, which holds the compiled
module of every program that ran (an ``HloProto`` under the stat
``Hlo Proto``, on the event named ``<program>(<fingerprint>)``, the name
the device's ``XLA Modules`` line gives the program).  Both are read
with a reader of protobuf's wire format, so that nothing beyond the
standard library is imported.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Set, Tuple

COLLECTIVE_OPCODES = frozenset({
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "ragged-all-to-all", "collective-broadcast",
    "all-reduce-start", "all-reduce-done", "all-gather-start",
    "all-gather-done", "collective-permute-start",
    "collective-permute-done"})
# instructions that run a called computation as one operation; a loop,
# a branch or a call runs its callee's instructions as operations instead
WRAPPERS = frozenset({"fusion", "async-start", "async-update", "async-done"})
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of each field of one serialized message:
    an int for a varint or fixed-width field, a ``memoryview`` for a
    length-delimited one."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wire} is not read here")
        yield num, value


def _ints(value) -> List[int]:
    """A repeated integer field's one entry: packed or single."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def _computations(module) -> Dict[int, Tuple[str, List[tuple]]]:
    """id -> (name, [(instruction name, opcode, called ids)]) of an
    ``HloModuleProto``."""
    comps = {}
    for num, comp in fields(module):
        if num != 3:                                    # computations
            continue
        name, cid, instrs = "", 0, []
        for f, v in fields(comp):
            if f == 1:
                name = bytes(v).decode()
            elif f == 5:
                cid = v
            elif f == 2:                                # instructions
                iname, op, called = "", "", []
                for g, w in fields(v):
                    if g == 1:
                        iname = bytes(w).decode()
                    elif g == 2:
                        op = bytes(w).decode()
                    elif g == 38:                       # called ids
                        called += _ints(w)
                instrs.append((iname, op, called))
        comps[cid] = (name, instrs)
    return comps


def collective_ops(module: bytes) -> List[Tuple[str, str, str]]:
    """(computation, ``%name``, kind) of every collective of a serialized
    ``HloModuleProto`` that runs as an operation of its own, in the
    module's order.  The kind is the collective's opcode, or the one
    its wrapper's computation holds, without ``-start``/``-done``."""
    comps = _computations(module)
    memo: Dict[int, str] = {}

    def held(cid: int) -> str:
        if cid not in memo:
            memo[cid] = ""
            for _, op, called in comps.get(cid, ("", []))[1]:
                kind = of(op, called)
                if kind:
                    memo[cid] = kind
                    break
        return memo[cid]

    def of(op: str, called) -> str:
        if op in COLLECTIVE_OPCODES:
            return op.replace("-start", "").replace("-done", "")
        if op in WRAPPERS:
            return next((k for k in map(held, called) if k), "")
        return ""

    inside = {c for _, instrs in comps.values()
              for _, op, called in instrs if op in WRAPPERS for c in called}
    return [(name, "%" + iname, kind)
            for cid, (name, instrs) in comps.items() if cid not in inside
            for iname, op, called in instrs
            for kind in [of(op, called)] if kind]


def entry_computation(module: bytes) -> str:
    """Name of a serialized ``HloModuleProto``'s entry computation."""
    return bytes(dict(fields(module)).get(2, b"")).decode()


def _file_varint(f) -> int:
    out = shift = 0
    while True:
        b = f.read(1)
        if not b:
            raise EOFError
        out |= (b[0] & 0x7F) << shift
        if b[0] < 0x80:
            return out
        shift += 7


def _metadata_plane(path: str):
    """The serialized ``/host:metadata`` plane of the trace at ``path``,
    or None.  Every other plane is skipped over in the file, unread."""
    with open(path, "rb") as f:
        while True:
            try:
                key = _file_varint(f)
            except EOFError:
                return None
            if key & 7 != 2:
                raise ValueError(f"field {key >> 3} of an XSpace is not "
                                 "length-delimited")
            size = _file_varint(f)
            start = f.tell()
            # a plane's id and name come before its lines
            if key >> 3 == 1 and \
                    _plane_name(f.read(min(size, 512))) == METADATA_PLANE:
                f.seek(start)
                return f.read(size)
            f.seek(start + size)


def trace_modules(path: str, prefix: str) -> Dict[str, bytes]:
    """Program name -> serialized ``HloModuleProto`` of every program in
    the trace at ``path`` whose name starts with ``prefix``; empty where
    the trace holds no compiled modules."""
    plane = _metadata_plane(path)
    out: Dict[str, bytes] = {}
    if plane is None:
        return out
    stat_ids = {}
    for num, entry in fields(plane):
        if num == 5:                                    # stat_metadata
            for f, v in fields(entry):
                if f == 2:
                    meta = dict(fields(v))
                    stat_ids[bytes(meta.get(2, b"")).decode()] = \
                        meta.get(1, 0)
    want = stat_ids.get(HLO_PROTO_STAT)
    if want is None:
        return out
    for num, entry in fields(plane):
        if num != 4:                                    # event_metadata
            continue
        for f, v in fields(entry):
            if f != 2:
                continue
            name, hlo = "", None
            for g, w in fields(v):
                if g == 2:
                    name = bytes(w).decode()
                elif g == 5:                            # stats
                    stat = dict(fields(w))
                    if stat.get(1) == want and 6 in stat:
                        hlo = stat[6]
            if name.startswith(prefix) and hlo is not None:
                module = dict(fields(hlo)).get(1)       # HloProto.module
                if module is not None:
                    out[name] = bytes(module)
    return out


def _plane_name(plane) -> str:
    """The name of a serialized XPlane, read from its first fields, which
    ``plane`` may hold alone."""
    try:
        for num, value in fields(plane):
            if num == 2:
                return bytes(value).decode()
            if num > 2:
                break
    except (IndexError, ValueError):
        pass
    return ""


def in_trace(path: str, prefix: str) -> Dict[str, Set[str]]:
    """Program name -> the ``%names`` of its collectives, for every
    program in the trace whose name starts with ``prefix``."""
    return {name: {op for _, op, _ in collective_ops(module)}
            for name, module in trace_modules(path, prefix).items()}
