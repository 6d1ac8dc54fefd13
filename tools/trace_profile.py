"""Trace overhead + identity + end-to-end tree profile.

Three gates, exercised against the same snapshot-storm workload that
tools/snapshot_profile.py uses (K-layer x M-pod prepare/commit storm over
a latency-simulating filesystem facade):

- **identity** — the storm's canonical metastore dump and normalized
  mount lists must be byte-identical traced vs untraced: tracing must
  never change what the control plane DOES;
- **overhead** — traced storm wall must stay within ``--max-overhead``
  percent (default 3%) of the untraced wall. Two complementary gates:
  the BEST of ``--reps`` paired back-to-back runs (wall noise on a
  loaded box is additive, so the best pair approaches true overhead
  from above), and a wall-noise-free analytic bound — every span the
  storm emits priced at the measured per-span cost. With tracing
  disabled the per-call cost of ``span()`` is reported in nanoseconds
  and gated at "a branch, not a feature";
- **tree** — one ``grpc.Prepare``-rooted demo trace on a lazy image must
  reconstruct a SINGLE tree spanning snapshotter → metastore → daemon
  mount/readiness → blobcache fetch, including a background readahead
  flight attributed to the root's trace id, and export as valid Chrome
  ``trace_event`` JSON.

Also reports span throughput (spans/sec into the ring) and ring drops,
and the convert verbs' tracing budget: spans a ``pack`` / ``merge`` (the
count must not follow the file count) and their cost a verb, priced at
the measured cost of one ``trace.Stages`` boundary with a profiler bridge
installed and of the collector clock a collection (gated at
``--max-verb-us``, default 1000).
Doubles as the CI smoke driver (``trace-smoke`` job, PYTHONDEVMODE=1) and
feeds ``bench.py``'s ``detail.trace``.

Usage: python tools/trace_profile.py [--pods 4] [--layers 4] [--json]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
from time import perf_counter, sleep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from nydus_snapshotter_tpu import constants as C  # noqa: E402
from nydus_snapshotter_tpu import trace  # noqa: E402
from nydus_snapshotter_tpu.daemon.blobcache import CachedBlob  # noqa: E402
from nydus_snapshotter_tpu.daemon.fetch_sched import FetchConfig  # noqa: E402
from nydus_snapshotter_tpu.parallel.pipeline import MemoryBudget  # noqa: E402
from nydus_snapshotter_tpu.snapshot.snapshotter import Snapshotter  # noqa: E402
from nydus_snapshotter_tpu.trace.export import to_chrome_trace  # noqa: E402
from tools.snapshot_profile import LatencyFs, run_storm  # noqa: E402

_CHROME_EVENT_KEYS = {"name", "cat", "ph", "ts", "dur", "pid", "tid", "args"}


# ---------------------------------------------------------------------------
# Micro: span throughput + disabled cost
# ---------------------------------------------------------------------------


def span_throughput(n: int = 20000, ring: int = 2048) -> dict:
    trace.configure(enabled=True, ring_capacity=ring, slow_op_threshold_ms=0)
    t0 = perf_counter()
    for _ in range(n):
        with trace.span("bench.op"):
            pass
    dt = perf_counter() - t0
    return {
        "spans": n,
        "spans_per_sec": round(n / dt),
        "ns_per_span": round(dt / n * 1e9),
        "ring_capacity": ring,
        "ring_dropped": trace.dropped(),
        "ring_len": len(trace.snapshot_spans()),
    }


def disabled_cost(n: int = 200000) -> dict:
    trace.configure(enabled=False)
    t0 = perf_counter()
    for _ in range(n):
        with trace.span("bench.op"):
            pass
    dt = perf_counter() - t0
    return {"calls": n, "ns_per_call": round(dt / n * 1e9, 1)}


def convert_verbs(n: int = 20000) -> dict:
    """Spans a served ``pack`` (fused lane, XLA on the CPU here) and a
    ``merge`` record, for a 16-file and a 400-file tar, and what they
    cost: spans x the measured cost of one stage boundary (span exit +
    the thread's usage read + next span enter + a no-op profiler
    annotation), plus the collections a verb ran x the collector clock's
    cost a collection (its two callback calls)."""
    import contextlib
    import gc
    import io
    import tarfile

    from nydus_snapshotter_tpu.cmd import convert as cli

    class _Annotation:  # what a TraceAnnotation costs with no session: nearly nothing
        def __init__(self, name):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    def tar_of(files: int) -> bytes:
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w") as tf:
            for i in range(files):
                data = bytes([i % 251]) * (300_000 if i < 2 else 1500)
                info = tarfile.TarInfo(f"d/f{i}")
                info.size = len(data)
                tf.addfile(info, io.BytesIO(data))
        return buf.getvalue()

    def collections() -> int:
        return sum(s["collections"] for s in gc.get_stats())

    work = tempfile.mkdtemp(prefix="ntpu_trace_convert.")
    counts = {}
    collected = {}
    try:
        for files in (16, 400):
            tar, blob = os.path.join(work, f"{files}.tar"), os.path.join(work, f"{files}.nydus")
            with open(tar, "wb") as f:
                f.write(tar_of(files))
            for verb, argv in (
                ("pack", ["pack", "--in", tar, "--out", blob, "--backend", "fused", "--chunk-size", "0x10000"]),
                ("merge", ["merge", "--out", blob + ".boot", blob]),
            ):
                trace.configure(enabled=True, slow_op_threshold_ms=0)
                before = collections()
                with contextlib.redirect_stdout(io.StringIO()):  # the verb's result line
                    rc = cli.main(["--jax-platform", "cpu", *argv])
                if rc != 0:
                    raise RuntimeError(f"cmd.convert {verb} exited {rc}")
                counts.setdefault(verb, []).append(len(trace.snapshot_spans()))
                collected.setdefault(verb, []).append(collections() - before)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    trace.configure(enabled=True, ring_capacity=2048, slow_op_threshold_ms=0)
    trace.install_profiler_bridge(_Annotation)
    try:
        with trace.batch_span("convert.pack"), trace.Stages() as stages:
            t0 = perf_counter()
            for _ in range(n):
                stages.next("pack:stage", bytes=1)
            dt = perf_counter() - t0
    finally:
        trace.install_profiler_bridge(None)
    ns = dt / n * 1e9
    t0 = perf_counter()
    for _ in range(n):
        trace._collector_clock("start", {})
        trace._collector_clock("stop", {})
    ns_gc = (perf_counter() - t0) / n * 1e9

    def us(verb: str) -> float:
        return round((max(counts[verb]) * ns + max(collected[verb]) * ns_gc) / 1e3, 1)

    return {
        "spans_per_pack": counts["pack"],
        "spans_per_merge": counts["merge"],
        "count_follows_files": len(set(counts["pack"])) != 1 or len(set(counts["merge"])) != 1,
        "ns_per_stage": round(ns),
        "collections_per_pack": collected["pack"],
        "ns_per_collection": round(ns_gc),
        "us_per_pack": us("pack"),
        "us_per_merge": us("merge"),
    }


# ---------------------------------------------------------------------------
# Storm: traced vs untraced identity + overhead
# ---------------------------------------------------------------------------


def storm_overhead(
    layers: int, pods: int, reps: int, mount_ms: float, ready_ms: float
) -> dict:
    base = tempfile.mkdtemp(prefix="ntpu-trace-profile-")
    walls = {"untraced": [], "traced": []}
    results: dict[str, tuple] = {}
    spans_per_storm = 0
    try:
        seq = 0
        for i in range(reps):
            # Alternate which mode runs first so warm-cache / drift bias
            # does not systematically favour one side.
            order = ("untraced", "traced") if i % 2 == 0 else ("traced", "untraced")
            for mode in order:
                if mode == "traced":
                    tracer = trace.configure(
                        enabled=True, ring_capacity=8192, slow_op_threshold_ms=0
                    )
                else:
                    tracer = trace.configure(enabled=False)
                seq += 1
                rep, dump, mounts = run_storm(
                    os.path.join(base, f"{mode}-{seq}"),
                    concurrent=True,
                    layers=layers,
                    pods=pods,
                    mount_ms=mount_ms,
                    ready_ms=ready_ms,
                )
                walls[mode].append(rep["wall_s"])
                results[mode] = (dump, mounts)
                if tracer is not None:
                    spans_per_storm = tracer.ring.pushes()
    finally:
        shutil.rmtree(base, ignore_errors=True)
        trace.configure(enabled=True)
    # The storm wall drifts tens of percent between reps on a loaded CI
    # box — far more than the span cost itself. Noise on this workload is
    # strictly additive (contention only ever slows a run), so the BEST
    # paired rep approaches the true overhead from above: each rep runs
    # both modes back to back, and we take the min of per-rep ratios.
    # A genuine span-cost regression shifts every rep's ratio up and is
    # additionally caught wall-noise-free by the analytic bound the
    # caller computes from spans_per_storm x ns_per_span.
    ratios = sorted(
        t / u for u, t in zip(walls["untraced"], walls["traced"])
    )
    return {
        "untraced_wall_s": round(min(walls["untraced"]), 4),
        "traced_wall_s": round(min(walls["traced"]), 4),
        "overhead_pct": round(max(0.0, ratios[0] - 1.0) * 100.0, 2),
        "median_ratio": round(ratios[len(ratios) // 2], 4),
        "rep_ratios": [round(r, 4) for r in ratios],
        "spans_per_storm": spans_per_storm,
        "identical": results["untraced"] == results["traced"],
        "reps": reps,
    }


# ---------------------------------------------------------------------------
# End-to-end tree: one Prepare-rooted trace across the planes
# ---------------------------------------------------------------------------


class TracedLatencyFs(LatencyFs):
    """LatencyFs with the same span names the real facade
    (filesystem/fs.py) emits at the daemon boundary."""

    def mount(self, sid, labels, snapshot):
        with trace.span("daemon.mount", sid=sid):
            super().mount(sid, labels, snapshot)

    def wait_until_ready(self, sid):
        with trace.span("daemon.wait_ready", sid=sid):
            super().wait_until_ready(sid)


def demo_tree(latency_ms: float = 1.0) -> dict:
    """Drive one lazy-image Prepare end to end under a single root span;
    verify the reconstructed tree and the Chrome export."""
    trace.configure(enabled=True, ring_capacity=4096, slow_op_threshold_ms=0)
    base = tempfile.mkdtemp(prefix="ntpu-trace-demo-")
    chunk = 16 << 10
    blob = bytes(range(256)) * (64 << 10 // 256) * 4  # 64 KiB * 4
    fetched = []

    def fetch(off: int, size: int) -> bytes:
        sleep(latency_ms / 1000.0)
        fetched.append((off, size))
        return blob[off : off + size]

    fs = TracedLatencyFs(mount_ms=1.0, ready_ms=4.0)
    sn = Snapshotter(
        root=os.path.join(base, "root"), fs=fs, prepare_fanout=2, usage_workers=1
    )
    cb = CachedBlob(
        os.path.join(base, "cache"),
        "demoblob0000",
        fetch,
        blob_size=len(blob),
        config=FetchConfig(
            fetch_workers=2, merge_gap=chunk, readahead=2 * chunk, budget_bytes=1 << 20
        ),
        budget=MemoryBudget(1 << 20),
    )
    try:
        with trace.span("grpc.Prepare", key="demo-ctr") as root:
            root_trace = root.span.trace_id
            meta_labels = {
                C.TARGET_SNAPSHOT_REF: "demo-meta",
                C.NYDUS_META_LAYER: "true",
                C.CRI_IMAGE_REF: "img-demo",
            }
            sn.prepare("demo-extract-meta", "", meta_labels)
            sn.commit("demo-meta", "demo-extract-meta", meta_labels)
            sn.prepare("demo-ctr", "demo-meta", {})
            sn.mounts("demo-ctr")  # joins the deferred wait_until_ready
            cb.read_at(0, chunk)  # cold miss: demand fetch
            cb.read_at(chunk, chunk)  # sequential: plans background readahead
    finally:
        cb.close()  # joins fetch workers (background flights land)
        sn.close()
        shutil.rmtree(base, ignore_errors=True)

    spans = [s for s in trace.snapshot_spans() if s.trace_id == root_trace]
    by_id = {s.span_id: s for s in spans}
    names = {s.name for s in spans}
    single_tree = all(not s.parent_id or s.parent_id in by_id for s in spans)
    background = [
        s for s in spans if s.name == "blobcache.fetch" and s.attrs.get("background")
    ]
    want = {
        "grpc.Prepare",
        "snapshot.prepare",
        "snapshot.prepare.bg",
        "metastore.create_snapshot",
        "metastore.commit_active",
        "daemon.mount",
        "daemon.wait_ready",
        "blobcache.read_at",
        "blobcache.fetch",
        "blobcache.readahead",
    }
    doc = to_chrome_trace(spans)
    doc = json.loads(json.dumps(doc))  # must survive a JSON round trip
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    chrome_ok = bool(events) and all(
        _CHROME_EVENT_KEYS <= set(e) for e in events
    )
    return {
        "trace_id": root_trace,
        "spans": len(spans),
        "span_names": sorted(names),
        "single_tree": single_tree,
        "missing_names": sorted(want - names),
        "background_readahead_attributed": bool(background),
        "chrome_export_valid": chrome_ok,
        "chrome_events": len(events),
        "remote_requests": len(fetched),
    }


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def profile(
    layers: int = 6,
    pods: int = 8,
    reps: int = 5,
    mount_ms: float = 3.0,
    ready_ms: float = 25.0,
) -> dict:
    report = {
        "throughput": span_throughput(),
        "disabled": disabled_cost(),
        "storm": storm_overhead(layers, pods, reps, mount_ms, ready_ms),
        "tree": demo_tree(),
        "convert": convert_verbs(),
    }
    # Wall-noise-free upper bound on the enabled overhead: every span the
    # storm emits, priced at the measured per-span cost, against the best
    # untraced wall — conservatively assumes NO span work hides under the
    # storm's mount/readiness waits.
    st = report["storm"]
    report["cost_bound_pct"] = round(
        st["spans_per_storm"]
        * report["throughput"]["ns_per_span"]
        / (st["untraced_wall_s"] * 1e9)
        * 100.0,
        2,
    )
    trace.reset()
    return report


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--pods", type=int, default=8)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--mount-ms", type=float, default=3.0)
    ap.add_argument("--ready-ms", type=float, default=25.0)
    ap.add_argument("--max-overhead", type=float, default=3.0,
                    help="max traced-vs-untraced storm overhead, percent")
    ap.add_argument("--max-disabled-ns", type=float, default=5000.0,
                    help="max per-call cost of span() with tracing disabled")
    ap.add_argument("--max-verb-us", type=float, default=1000.0,
                    help="max tracing cost of one convert verb, microseconds")
    ap.add_argument("--json", action="store_true", help="machine-readable output")
    args = ap.parse_args()

    report = profile(
        layers=args.layers, pods=args.pods, reps=args.reps,
        mount_ms=args.mount_ms, ready_ms=args.ready_ms,
    )
    leaked = [
        t.name for t in threading.enumerate()
        if t.name.startswith(("ntpu-snap", "ntpu-fetch"))
    ]
    report["leaked_threads"] = leaked

    if args.json:
        print(json.dumps(report))
    else:
        st = report["storm"]
        print(f"storm ({args.layers}x{args.pods}, best pair of {args.reps}): "
              f"untraced {st['untraced_wall_s']:.3f}s traced "
              f"{st['traced_wall_s']:.3f}s overhead {st['overhead_pct']}% "
              f"(cost bound {report['cost_bound_pct']}%, "
              f"{st['spans_per_storm']} spans/storm) "
              f"identical={st['identical']}")
        tp = report["throughput"]
        print(f"throughput: {tp['spans_per_sec']} spans/s "
              f"({tp['ns_per_span']} ns/span), ring dropped {tp['ring_dropped']}")
        print(f"disabled: {report['disabled']['ns_per_call']} ns/call")
        cv = report["convert"]
        print(f"convert: {cv['spans_per_pack']} spans/pack {cv['spans_per_merge']} "
              f"spans/merge (16 and 400 files), {cv['ns_per_stage']} ns/stage, "
              f"{cv['collections_per_pack']} collections/pack at {cv['ns_per_collection']} ns = "
              f"{cv['us_per_pack']} us/pack, {cv['us_per_merge']} us/merge")
        tr = report["tree"]
        print(f"tree: {tr['spans']} spans single_tree={tr['single_tree']} "
              f"background_readahead={tr['background_readahead_attributed']} "
              f"chrome_valid={tr['chrome_export_valid']} "
              f"missing={tr['missing_names']}")

    tr = report["tree"]
    if not report["storm"]["identical"]:
        print("FAIL: traced storm results diverge from untraced", file=sys.stderr)
        return 1
    if report["storm"]["overhead_pct"] > args.max_overhead:
        print(
            f"FAIL: traced overhead {report['storm']['overhead_pct']}% > "
            f"{args.max_overhead}%",
            file=sys.stderr,
        )
        return 1
    if report["cost_bound_pct"] > args.max_overhead:
        print(
            f"FAIL: span cost bound {report['cost_bound_pct']}% > "
            f"{args.max_overhead}% "
            f"({report['storm']['spans_per_storm']} spans/storm at "
            f"{report['throughput']['ns_per_span']}ns)",
            file=sys.stderr,
        )
        return 1
    cv = report["convert"]
    if cv["count_follows_files"] or max(cv["spans_per_pack"]) > 24 or max(cv["spans_per_merge"]) > 8:
        print(f"FAIL: convert span budget (24 a pack, 8 a merge, whatever the "
              f"file count): {cv}", file=sys.stderr)
        return 1
    if max(cv["us_per_pack"], cv["us_per_merge"]) > args.max_verb_us:
        print(f"FAIL: tracing costs {cv['us_per_pack']} us a pack > "
              f"{args.max_verb_us} us", file=sys.stderr)
        return 1
    if report["disabled"]["ns_per_call"] > args.max_disabled_ns:
        print(
            f"FAIL: disabled span() costs {report['disabled']['ns_per_call']}ns "
            f"> {args.max_disabled_ns}ns",
            file=sys.stderr,
        )
        return 1
    if not (
        tr["single_tree"]
        and tr["background_readahead_attributed"]
        and tr["chrome_export_valid"]
        and not tr["missing_names"]
    ):
        print(f"FAIL: demo trace tree incomplete: {tr}", file=sys.stderr)
        return 1
    if leaked:
        print(f"FAIL: leaked worker threads {leaked}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
