"""The executor-side spans wrap the program's own library in place: every
symbol it registers is timed under its own name, the symbols and their
order stay the program's, and dropping the wrappers lets go of what the
functions are bound to."""
import numpy as np

from chipbench import spans as S
from repro.core import FunctionLibrary


def _library():
    lib = FunctionLibrary("llm")
    lib.register("prefill", lambda p: {"sid": 7,
                                       "next_token": np.zeros(2, np.int32)})
    lib.register("decode", lambda p: {"next_token": np.ones(2, np.int32)})
    lib.register("close_session", lambda p: {"ok": True})
    lib.register("extra", lambda p: p)
    return lib


def test_every_registered_function_is_timed_under_its_name():
    lib = _library()
    symbols, version = lib.symbols, lib.version
    sp = S.Spans()
    drop = S.time_library(lib, sp)
    assert lib.symbols == symbols and lib.version == version
    call = lambda name, p: lib.by_index(lib.index_of(name))(p)  # noqa: E731
    assert call("prefill", {"tokens": np.zeros((2, 5), np.int32)})["sid"] == 7
    call("decode", {"sid": 7})
    call("decode", {"sid": 7})
    assert call("extra", 3) == 3
    call("close_session", {"sid": 7})
    assert [s.name for s in sp.items] == [
        "exec.prefill", "exec.decode", "exec.decode", "exec.extra",
        "exec.close_session"]
    assert [s.meta for s in sp.items[:3]] == [
        {"rows": 2, "ctx": 5}, {"rows": 2, "ctx": 6}, {"rows": 2, "ctx": 7}]
    assert all(s.end >= s.start for s in sp.items)
    drop()
    try:
        call("decode", {"sid": 7})
    except KeyError:
        pass
    else:
        raise AssertionError("a dropped library still calls through")
