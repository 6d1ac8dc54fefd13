"""The readers of the program's spans, on hand-made span lists, and one
rehearsal of the `dict` mix through run.main that has to find every new metric."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import program_spans, run  # noqa: E402
from benchmark.readers import span_attr_share, span_seconds  # noqa: E402
from benchmark.tests.test_rehearsal import run_cell, tiny  # noqa: E402,F401

GIB = 2**30
NEW = ["pack_read_scan_s_per_gib", "pack_dict_load_s_per_gib", "pack_dedup_s_per_gib",
       "pack_compress_write_s_per_gib", "pack_bootstrap_s_per_gib", "lane_d2h_s_per_gib",
       "pack_unattributed_s_per_gib", "merge_read_parse_s_per_image", "pass2_useful_block_share",
       "setup_lane_first_calls_s"]


@pytest.fixture(autouse=True)
def fresh_ring():
    """A run is a process of its own; here many share one, and a ring that
    dropped a span of an earlier test would (rightly) silence every reader."""
    from nydus_snapshotter_tpu import trace

    trace.reset()
    yield
    trace.reset()


def record(verb, t0, t1, nbytes=0, ok=True):
    return {"verb": verb, "t0": t0, "t1": t1, "bytes": nbytes, "ok": ok}


def pack_spans(t0, scale=1.0, real=60, padded=100):
    """A pack of 10 x scale seconds: root, five leaves, 1 x scale second that no leaf covers."""
    at = lambda a, b, name, parent="convert.pack", **attrs: (name, parent, t0 + a * scale, t0 + b * scale, attrs)
    return [at(0, 10, "convert.pack", ""), at(0, 1, "pack:read"), at(1, 3, "pack:scan"),
            at(3, 4, "pack:lane.pass1"), at(4, 4.5, "pack:lane.plan", blocks_real=real, blocks_padded=padded),
            at(4.5, 6.5, "pack:lane.pass2"), at(6.5, 9, "pack:dedup"),
            at(7, 8, "convert.chunk.worker", "pack:dedup")]  # a leaf's own child is nobody's leaf


@pytest.fixture
def ctx():
    spans = (pack_spans(0.0, scale=3.0)  # the warm-up convert: before the window
             + pack_spans(100.0) + pack_spans(120.0, real=10, padded=100)
             + [("convert.merge", "", 140.0, 142.0, {}), ("merge:read", "convert.merge", 140.0, 141.0, {}),
                ("merge:parse", "convert.merge", 141.0, 141.5, {})]
             + pack_spans(200.0))  # a failed pack: not in the window's sums
    records = [record("pack", 99.9, 110.1, GIB), record("pack", 119.9, 130.1, GIB), record("merge", 139.9, 142.1),
               record("pack", 199.9, 210.1, GIB, ok=False)]
    return {"records": records, "spans": (spans, 0)}


def test_window_spans_per_gib_and_per_image(ctx):
    assert span_seconds.read(ctx, ["pack:read", "pack:scan"], "gib") == pytest.approx(3.0)
    assert span_seconds.read(ctx, ["pack:dedup"], "gib") == pytest.approx(2.5)
    assert span_seconds.read(ctx, ["merge:read", "merge:parse"], "image") == pytest.approx(1.5)
    assert span_seconds.read(ctx, ["pack:dict_load"], "gib") is None  # no such span: the metric is left out
    assert span_seconds.read({"records": [], "spans": ctx["spans"]}, ["pack:read"], "gib") is None


def test_root_self_time_is_root_less_its_own_leaves(ctx):
    # 10 s root - (1 + 2 + 1 + 0.5 + 2 + 2.5) s of leaves; the worker under pack:dedup is not subtracted twice
    assert span_seconds.read(ctx, ["<root self>"], "gib") == pytest.approx(1.0)
    assert span_seconds.read(ctx, ["<root self>"], "image") == pytest.approx(0.5)


def test_setup_spans_ended_before_the_windows_first_record(ctx):
    assert span_seconds.read(ctx, ["pack:lane.pass1", "pack:lane.pass2"], when="setup") == pytest.approx(9.0)
    assert span_seconds.read(ctx, ["merge:read"], when="setup") is None


def test_a_ring_that_dropped_reads_nothing(ctx):
    ctx["spans"] = (ctx["spans"][0], 1)
    assert span_seconds.read(ctx, ["pack:read"], "gib") is None
    assert span_seconds.read(ctx, ["pack:lane.pass1"], when="setup") is None
    assert span_attr_share.read(ctx, "pack:lane.plan", "blocks_real", "blocks_padded") is None


def test_attr_share_sums_before_it_divides(ctx):
    assert span_attr_share.read(ctx, "pack:lane.plan", "blocks_real", "blocks_padded") == pytest.approx(35.0)
    assert span_attr_share.read(ctx, "pack:scan", "blocks_real", "blocks_padded") is None


def test_a_program_without_the_spans_gives_nothing(monkeypatch):
    """The parent commit: spans with no public perf_counter start."""
    from nydus_snapshotter_tpu import trace

    class Old:
        name, span_id, parent_id, duration_ms, attrs = "pack:read", 1, 0, 5.0, {}

    monkeypatch.setattr(trace, "snapshot_spans", lambda: [Old()])
    assert program_spans.finished() == ([], 0)
    assert span_seconds.read({"records": [record("pack", 0, 1, GIB)]}, ["pack:read"], "gib") is None


def test_dict_rehearsal_reports_every_new_metric(tiny, capfd):  # noqa: F811
    rc, out = run_cell(capfd, "--workload", "node21-64k.dict", "--seed", "2000000089", "--seconds", "6",
                       "--trace", "1")
    assert rc == 0
    last = json.loads(out[-1])
    assert last["correct"] is True, last["checks"]
    assert last["checks"]["result_lines_differ"]["value"] == 0
    assert set(NEW) <= set(last["metrics"]), sorted(set(NEW) - set(last["metrics"]))
    value = lambda name: last["metrics"][name]["value"]
    assert all(value(n) > 0 for n in NEW if n != "pack_unattributed_s_per_gib")
    assert 0 < value("pass2_useful_block_share") <= 100
    # the parts are the whole: the subtraction and the spans time the same packs. The subtraction also
    # holds digest D2H (the second half of lane_d2h_s_per_gib; none of its five counters covers it) and
    # what main() does around the root span (argument parsing, the result line): ~3 ms a verb, a tenth
    # of a 3 MiB pack here and a thousandth of a 0.5 GiB one on the chip, where the parts meet within 3%
    parts = sum(value(n) for n in NEW[:5] + ["pack_unattributed_s_per_gib"])
    whole = value("host_outside_lane_s_per_gib")
    assert parts <= whole and parts + value("lane_d2h_s_per_gib") >= whole * 0.85
    bench = run.load(ROOT, "BENCHMARK.json")
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == NEW
