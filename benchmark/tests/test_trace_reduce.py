"""The reduction from a profiler trace to busy/idle seconds, on the small
trace recorded on a v5e (benchmark/tools/record_trace.py) and on intervals
whose union is known."""

import gzip
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402

RECORDED = os.path.join(ROOT, "benchmark", "testdata", "small.xplane.pb.gz")


def test_union_counts_nested_and_overlapping_once():
    #           a while [0, 10) with its children inside, an overlap, a gap, a lone op
    starts = np.array([0.0, 1.0, 4.0, 8.0, 20.0]) * 1e9
    ends = np.array([10.0, 3.0, 6.0, 12.0, 21.0]) * 1e9
    seconds, ms, me = trace_reduce.union_seconds(starts, ends)
    assert seconds == 13.0
    assert list(ms / 1e9) == [0.0, 20.0] and list(me / 1e9) == [12.0, 21.0]
    assert trace_reduce.union_seconds(np.zeros(0), np.zeros(0))[0] == 0.0


def test_short_op_names():
    assert trace_reduce._short_op(
        "%fusion.3 = s32[131072]{0:T(1024)S(1)} fusion(s32[20971520]{0:T(1024)} %bitcast.61), kind=kLoop"
    ) == "%fusion.3 fusion s32[131072]"
    assert trace_reduce._short_op(
        "%while.1 = (u32[]{:T(128)}, u8[64]{0:T(1024)(128)(4,1)}) while((u32[]{:T(128)}) %tuple.18), condition=%c"
    ) == "%while.1 while tuple"


def test_recorded_trace_gives_the_known_split(tmp_path):
    path = tmp_path / "small.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    got = trace_reduce.reduce_file(str(path))
    assert got["chips"] == 1
    assert [name for name, _s in got["spans"]] == ["pack:0", "pack:1", "merge"]
    assert got["busy_s"] == pytest.approx(KNOWN["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(KNOWN["window_s"], rel=1e-9)
    assert 0 < got["busy_s"] < got["window_s"]
    # busy is a union: the plain sum of the op events counts every loop body twice
    assert sum(s for name, s in got["device_ops"] if name.startswith("program ")) >= got["busy_s"] * 0.99
    assert got["device_ops"][0][0] == "program jit__pass2"
    gaps = dict(got["idle_gaps"])
    assert any(name.startswith("merge:") for name in gaps) and any(name.startswith("pack:0: start") for name in gaps)
    assert sum(gaps.values()) <= got["window_s"] - got["busy_s"] + 1e-9


KNOWN = {"busy_s": 0.010411196, "window_s": 0.121084701}  # 8.6% busy: a tiny image is nearly all host
