"""The readers of what a leaf's thread did (`span_usage_share`,
`span_usage_per`) on hand-made span lists, the four metrics that use them in
BENCHMARK.json, and one traced rehearsal of the fan-out cell that has to find
all four. In a file of its own: a PR that is not a `benchmark` PR adds files,
edits none."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.readers import span_usage_per, span_usage_share  # noqa: E402
from benchmark.tests.test_rehearsal import run_cell, tiny  # noqa: E402,F401

GIB = 2**30
NEW = ["lane_host_cpu_share", "lane_host_preempted_share", "pack_per_file_cpu_us", "pack_gc_s_per_gib"]
PER_FILE = ["pack:scan", "pack:lane.resolve", "pack:lane.plan", "pack:dedup", "pack:bootstrap"]


def record(verb, t0, t1, nbytes=0, ok=True):
    return {"verb": verb, "t0": t0, "t1": t1, "bytes": nbytes, "ok": ok}


def use(cpu, waits, preempts, gc):
    return {"cpu_s": cpu, "waits": waits, "preempts": preempts, "gc_s": gc}


def pack_spans(t0, files=1000, usage=True, scale=1.0):
    """A pack of 10 x scale seconds. Its leaves' usage (when ``usage``):
    resolve 2 s wall, 1 s CPU, 3 waits, 1 preempt; plan 0.5 s wall, 0.5 s
    CPU, 0 and 1; the per-file leaves 3.1 s of CPU in all; 0.25 s of
    collections over every pack:* leaf."""
    def at(a, b, name, u=None, **attrs):
        return (name, "convert.pack", t0 + a * scale, t0 + b * scale, {**attrs, **(use(*u) if usage and u else {})})

    return [("convert.pack", "", t0, t0 + 10 * scale, {}),
            at(0, 1, "pack:read", (0.4, 2, 0, 0.0)),
            at(1, 2, "pack:scan", (0.9, 1, 0, 0.1), members=7, files_planned=files),
            at(2, 4, "pack:lane.resolve", (1.0, 3, 1, 0.0), files=files),
            at(4, 4.5, "pack:lane.plan", (0.5, 0, 1, 0.0)),
            at(4.5, 6.5, "pack:lane.pass2", (0.01, 1, 0, 0.0)),
            at(6.5, 8, "pack:dedup", (0.4, 0, 0, 0.15)),
            at(8, 9, "pack:compress_write", (0.2, 40, 2, 0.0)),
            at(9, 10, "pack:bootstrap", (0.3, 0, 0, 0.0)),
            ("convert.chunk.worker", "pack:dedup", t0 + 7, t0 + 8, {})]  # a plain span under a leaf


def ctx_of(*packs, dropped=0):
    spans = pack_spans(0.0, scale=3.0)  # the warm-up: before the window
    records = []
    for i, (kw, ok) in enumerate(packs):
        spans += pack_spans(100.0 + 20 * i, **kw)
        records.append(record("pack", 99.9 + 20 * i, 110.1 + 20 * i, GIB, ok))
    return {"records": records, "spans": (spans, dropped)}


def metric(ctx, name):
    spec = run.load(run.HERE, "metrics", f"{name}.json")
    reader = {"span_usage_share": span_usage_share, "span_usage_per": span_usage_per}[spec["reader"]]
    return reader.read(ctx, **spec["params"])


def test_each_metric_sums_before_it_divides():
    # two packs of the window (1,000 and 3,000 files); the failed pack and the warm-up are out
    ctx = ctx_of(({}, True), ({"files": 3000}, True), ({"files": 10**6}, False))
    assert metric(ctx, "lane_host_cpu_share") == pytest.approx(100 * 2 * 1.5 / (2 * 2.5))
    assert metric(ctx, "lane_host_preempted_share") == pytest.approx(100 * 2 * 2 / (2 * 5))
    assert metric(ctx, "pack_per_file_cpu_us") == pytest.approx(1e6 * 2 * 3.1 / 4000)
    assert metric(ctx, "pack_gc_s_per_gib") == pytest.approx(2 * 0.25 / 2)


def test_a_window_with_no_collection_or_no_switch_reads_zero():
    ctx = ctx_of(({}, True))
    for s in ctx["spans"][0]:
        if "gc_s" in s[4]:
            s[4]["gc_s"] = 0.0
            s[4]["waits"] = s[4]["preempts"] = 0
    assert metric(ctx, "pack_gc_s_per_gib") == 0.0
    assert metric(ctx, "lane_host_preempted_share") == 0.0
    assert metric(ctx, "lane_host_cpu_share") == pytest.approx(100 * 1.5 / 2.5)


def test_a_leaf_without_the_usage_is_left_out_and_none_reads_none():
    # the parent's program: no leaf reads usage, every metric is left out of the line
    ctx = ctx_of(({"usage": False}, True))
    assert all(metric(ctx, n) is None for n in NEW)
    # one pack with, one without: the one without weighs nothing, wall and files included
    ctx = ctx_of(({}, True), ({"usage": False, "files": 3000}, True))
    assert metric(ctx, "lane_host_cpu_share") == pytest.approx(100 * 1.5 / 2.5)
    assert metric(ctx, "pack_gc_s_per_gib") == pytest.approx(0.25 / 2)


def test_none_without_records_a_count_or_a_whole_ring():
    assert all(metric(ctx_of(({}, True), dropped=1), n) is None for n in NEW)
    assert all(metric({"records": [], "spans": ctx_of(({}, True))["spans"]}, n) is None for n in NEW)
    assert metric(ctx_of(({"files": 0}, True)), "pack_per_file_cpu_us") is None
    ctx = ctx_of(({}, True))
    assert span_usage_share.read(ctx, ["pack:no_such"], ["cpu_s"]) is None
    assert span_usage_per.read(ctx, "cpu_s", names=PER_FILE, span="pack:no_such", count="files_planned") is None


def test_the_metrics_are_in_the_benchmark_and_name_their_readers():
    bench = run.load(ROOT, "BENCHMARK.json")
    got = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}  # by name: later PRs append
    assert sorted(got) == sorted(NEW)
    layer_of = {m["name"]: m["layer"] for m in bench["per_layer"]}
    for name, m in got.items():
        assert "workloads" not in m and m["moves"] == "convert_mib_per_s" and m["source"] == "program_span"
    assert got["lane_host_cpu_share"]["layer"] == got["lane_host_preempted_share"]["layer"] == layer_of["lane_host_s_per_gib"]
    assert got["pack_per_file_cpu_us"]["layer"] == got["pack_gc_s_per_gib"]["layer"] == layer_of["pack_per_file_host_us"]
    # pack_per_file_cpu_us reads pack_per_file_host_us's own leaves and divisor
    host = run.load(run.HERE, "metrics", "pack_per_file_host_us.json")["params"]
    cpu = run.load(run.HERE, "metrics", "pack_per_file_cpu_us.json")["params"]
    assert (cpu["names"], cpu["span"], cpu["count"], cpu["scale"]) == (host["names"], host["span"], host["attr"],
                                                                       host["scale"])


def test_fanout_rehearsal_reports_the_four(tiny, capfd):  # noqa: F811
    rc, out = run_cell(capfd, "--workload", "node21-64k.fanout", "--seed", "2000000111", "--seconds", "6",
                       "--trace", "1")
    assert rc == 0
    last = json.loads(out[-1])
    assert last["correct"] is True, last["checks"]
    # the fan-out reports them per layer under names of its own, moving pack_p95_s_per_gib (PERF.md §2)
    fanout = [f"{n}.fanout" for n in NEW]
    assert set(fanout) <= set(last["metrics"]), sorted(set(fanout) - set(last["metrics"]))
    value = lambda name: last["metrics"][f"{name}.fanout"]["value"]
    assert 0 < value("lane_host_cpu_share") <= 100.5  # a thread's CPU is never more than its wall
    assert 0 <= value("lane_host_preempted_share") <= 100
    assert 0 < value("pack_per_file_cpu_us") <= value("pack_per_file_host_us") * 1.005
    assert value("pack_gc_s_per_gib") >= 0
