"""The trace reduction, on a small trace recorded on a TPU v5e (the
harness's own run of a two-layer cell: decode scans and chunk prefills
through both Pallas kernels) and on hand-made intervals."""
import lzma
import pathlib

import pytest

from bench import trace_reduce as T

# xz-compressed: the raw trace is ten times larger
TRACE = pathlib.Path(__file__).resolve().parent / "data" / \
    "v5e_small_cell.xplane.pb.xz"


def test_names():
    assert T.program_name("jit_run(4293513019402158429)") == "jit_run"
    assert T.op_name("%_paged_decode.10 = (f32[12,8,8,128]) custom-call("
                     "s32[12,24] %x)") == "_paged_decode"
    assert T.op_name("%while.54 = (s32[]) while((s32[]) %t)") == "while"
    assert T.op_name("dot_general.1") == "dot_general"


def test_self_time_of_nested_operations():
    # a loop [0, 100) holding two body operations and a nested kernel
    ops = [(0, 100, "loop"), (10, 40, "body_a"), (20, 30, "kernel"),
           (50, 90, "body_b"), (120, 130, "after")]
    got = {name: d for _, d, name in T._self_times(ops)}
    assert got == {"loop": 30, "body_a": 20, "kernel": 10, "body_b": 40,
                   "after": 10}


def test_union_and_clip():
    u = T._union([[5, 9], [0, 3], [2, 4], [8, 12]])
    assert u == [[0, 4], [5, 12]]
    assert T._clip(u, 3, 10) == [[3, 4], [5, 10]]


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    raw = tmp_path_factory.mktemp("trace") / "v5e_small_cell.xplane.pb"
    raw.write_bytes(lzma.decompress(TRACE.read_bytes()))
    return T.reduce(raw)


def test_recorded_trace_programs_and_kernels(summary):
    s = summary
    assert s.chips == 1
    assert {"jit_run", "jit_chunk_prefill"} <= set(s.program_s)
    kernels = {k for k in s.op_s if k.endswith(("/_paged_decode",
                                                "/_paged_prefill"))}
    assert "jit_run/_paged_decode" in kernels
    assert "jit_chunk_prefill/_paged_prefill" in kernels
    # two layers: the decode kernel runs twice a decode step
    assert s.op_n["jit_run/_paged_decode"] % 2 == 0


def test_recorded_trace_time_adds_up(summary):
    s = summary
    assert 0 < s.busy_s <= s.window_s
    idle = sum(s.idle_by_span.values())
    assert idle + s.busy_s == pytest.approx(s.window_s, rel=1e-6)
    # self times of one chip's operations cannot exceed its busy time
    # by more than the operations that overlap (async copies do)
    assert sum(s.op_s.values()) <= 1.5 * s.busy_s
    assert s.spans["step"][0] > 0


def test_breakdown_shape(summary):
    b = T.breakdown(summary)
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    secs = [v for _, v in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert all(name in ("step", "submit", "bookkeeping", "other")
               for name, _ in b["idle_gaps"])
