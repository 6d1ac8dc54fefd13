"""The reader `span_seconds_per_attr` (seconds of named leaves a counted
thing) on hand-made span lists, and PR 29's metric in BENCHMARK.json.
In a file of its own: a PR that is not a `benchmark` PR adds files, edits none."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.readers import span_seconds_per_attr  # noqa: E402

GIB = 2**30
PER_FILE = ["pack:scan", "pack:lane.resolve", "pack:lane.plan", "pack:dedup", "pack:bootstrap"]


def record(verb, t0, t1, nbytes=0, ok=True):
    return {"verb": verb, "t0": t0, "t1": t1, "bytes": nbytes, "ok": ok}


def pack_spans(t0, files=None, single=None, scale=1.0):
    """A pack of 10 x scale seconds; its five per-file leaves take 1 + 2 + 0.5 + 1.5 + 1 = 6 x scale."""
    at = lambda a, b, name, **attrs: (name, "convert.pack", t0 + a * scale, t0 + b * scale, attrs)
    scan = {"members": 7} if files is None else {"members": 7, "files_planned": files}
    resolve = {"files": files or 0} if single is None else {"files": files, "single_chunk_files": single}
    return [("convert.pack", "", t0, t0 + 10 * scale, {}), at(0, 1, "pack:read"), at(1, 2, "pack:scan", **scan),
            at(2, 4, "pack:lane.resolve", **resolve), at(4, 4.5, "pack:lane.plan"), at(4.5, 6.5, "pack:lane.pass2"),
            at(6.5, 8, "pack:dedup"), at(8, 9, "pack:compress_write"), at(9, 10, "pack:bootstrap")]


def ctx_of(*packs, dropped=0):
    spans = pack_spans(0.0, files=1000, single=900, scale=3.0)  # the warm-up: before the window
    records = []
    for i, (kw, ok) in enumerate(packs):
        spans += pack_spans(100.0 + 20 * i, **kw)
        records.append(record("pack", 99.9 + 20 * i, 110.1 + 20 * i, GIB, ok))
    return {"records": records, "spans": (spans, dropped)}


def read(ctx):
    return span_seconds_per_attr.read(ctx, PER_FILE, "pack:scan", "files_planned", scale=1e6)


def test_the_quotient_sums_before_it_divides():
    # two packs of the window: 12 s of per-file leaves over 1,000 + 3,000 files; the failed pack and the warm-up are out
    ctx = ctx_of(({"files": 1000, "single": 900}, True), ({"files": 3000, "single": 2400}, True),
                 ({"files": 10**6, "single": 0}, False))
    assert read(ctx) == pytest.approx(12.0 / 4000 * 1e6)
    assert span_seconds_per_attr.read(ctx, ["pack:dedup"], "pack:scan", "files_planned") == pytest.approx(3.0 / 4000)


def test_none_without_the_attribute_the_span_or_a_count():
    assert read(ctx_of(({}, True))) is None  # pack:scan has no files_planned
    assert read(ctx_of(({"files": 0, "single": 0}, True))) is None  # nothing counted: no quotient
    ctx = ctx_of(({"files": 1000, "single": 900}, True))
    assert span_seconds_per_attr.read(ctx, ["pack:no_such"], "pack:scan", "files_planned") is None
    assert span_seconds_per_attr.read(ctx, PER_FILE, "pack:no_such", "files_planned") is None
    assert read({"records": [], "spans": ctx["spans"]}) is None


def test_a_ring_that_dropped_reads_nothing():
    assert read(ctx_of(({"files": 1000, "single": 900}, True), dropped=1)) is None


def test_the_metric_is_in_the_benchmark_and_names_its_reader():
    bench = run.load(ROOT, "BENCHMARK.json")
    (metric,) = [m for m in bench["per_layer"] if m["name"] == "pack_per_file_host_us"]  # by name: later PRs append
    assert "workloads" not in metric and metric["moves"] == "convert_mib_per_s" and metric["source"] == "program_span"
    assert metric["layer"] in {m["layer"] for m in bench["per_layer"] if m["name"] == "host_outside_lane_s_per_gib"}
    spec = run.load(run.HERE, "metrics", "pack_per_file_host_us.json")
    assert spec["reader"] == "span_seconds_per_attr" and spec["params"]["names"] == PER_FILE
    assert read(ctx_of(({"files": 2000, "single": 1}, True))) == pytest.approx(
        span_seconds_per_attr.read(ctx_of(({"files": 2000, "single": 1}, True)), **spec["params"]))
