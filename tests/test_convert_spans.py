"""The convert verbs' span tree (docs/observability.md): `pack` and `merge`
are flat partitions of their wall into named leaf spans, the stage counters
and `stats` are fed from the spans' own times, tracing changes no artifact,
and the profiler bridge needs no JAX inside `trace`.

CPU backend: the fused lane runs its XLA formulation (`--jax-platform cpu`).
"""

import io
import json
import os
import subprocess
import sys
import tarfile

import numpy as np
import pytest

from nydus_snapshotter_tpu import trace
from nydus_snapshotter_tpu.cmd import convert as cli
from nydus_snapshotter_tpu.converter.convert import Merge, Pack
from nydus_snapshotter_tpu.converter.types import MergeOption, PackOption
from nydus_snapshotter_tpu.ops import fused_convert, native_cdc

CHUNK = 0x10000
# the device lane starts when the layer is read: its first half (the upload
# and pass 1 enqueued) comes before the dictionary and the scan, the wait
# for pass 1 and the rest after them
LANE_BEGIN = [f"pack:lane.{s}" for s in ("layout", "h2d", "pass1")]
LANE = [f"pack:lane.{s}" for s in ("pass1", "cand_d2h", "resolve", "plan", "pass2", "digest_d2h")]
TAIL = ["pack:dedup", "pack:compress_write", "pack:bootstrap"]
WHOLE_LAYER = "pack:fused_pack" if native_cdc.pack_files_available() else "pack:chunk_digest"
# the leaves of one `pack`, in order, by (backend, with a chunk dict)
PACK_LEAVES = {
    ("fused", False): ["pack:read", "pack:open_out", *LANE_BEGIN, "pack:scan", *LANE, *TAIL],
    ("fused", True): ["pack:read", "pack:open_out", *LANE_BEGIN, "pack:dict_load", "pack:scan", *LANE, *TAIL],
    # one thread: the whole-layer native pass without a dictionary (where
    # the native engine is built; the per-file lane where it is not), the
    # chunk+digest sweep and the Python dedup lane with one
    ("hybrid", False): ["pack:read", "pack:open_out", "pack:scan", WHOLE_LAYER, *TAIL],
    ("hybrid", True): ["pack:read", "pack:open_out", "pack:dict_load", "pack:scan", "pack:chunk_digest", *TAIL],
}
MERGE_LEAVES = ["merge:read", "merge:parse", "merge:overlay", "merge:emit", "merge:emit"]
# what each recorded leaf's thread did inside it (trace.Stages)
USAGE = {"cpu_s", "waits", "preempts", "gc_s"}
CASES = [(b, d) for b in ("fused", "hybrid") for d in (False, True)]


def make_tar(n_files: int, seed: int = 1, big: int = 4) -> bytes:
    """`big` files that CDC cuts, the rest small; same seed, same leading files."""
    rng = np.random.default_rng(seed)
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tf:
        for i in range(n_files):
            size = int(rng.integers(200_000, 600_000)) if i < big else int(rng.integers(100, 3000))
            info = tarfile.TarInfo(f"d{i % 7}/f{i}")
            info.size = size
            tf.addfile(info, io.BytesIO(rng.integers(0, 256, size, dtype=np.uint8).tobytes()))
    return buf.getvalue()


@pytest.fixture(autouse=True)
def tracer(monkeypatch):
    """A fresh ring a test, one pack thread (the lane a host pack takes
    follows the thread count), no profiler bridge left behind."""
    monkeypatch.setenv("NTPU_PACK_THREADS", "1")
    trace.configure(enabled=True)
    yield
    trace.install_profiler_bridge(None)
    trace.reset()


@pytest.fixture(scope="module")
def work(tmp_path_factory):
    d = tmp_path_factory.mktemp("spans")
    (d / "a.tar").write_bytes(make_tar(20, seed=1))
    (d / "b.tar").write_bytes(make_tar(20, seed=2))
    (d / "many.tar").write_bytes(make_tar(2000, seed=1))
    return d


def run_cli(*argv) -> None:
    assert cli.main(["--jax-platform", "cpu", *argv]) == 0


def pack(work, backend: str, name: str = "a", with_dict: bool = False) -> str:
    out = str(work / f"{name}.{backend}.nydus")
    extra = ["--chunk-dict", dict_boot(work)] if with_dict else []
    run_cli("pack", "--in", str(work / f"{name}.tar"), "--out", out, "--backend", backend,
            "--chunk-size", hex(CHUNK), *extra)
    return out


def dict_boot(work) -> str:
    """Image b's merged bootstrap, the dictionary of the `with a dict` cases."""
    boot = str(work / "b.boot")
    if not os.path.exists(boot):
        run_cli("merge", "--out", boot, pack(work, "hybrid", "b"))
    return boot


def tree(root_name: str):
    """-> (the last root of that name, its leaves in start order)."""
    spans = trace.snapshot_spans()
    root = [s for s in spans if s.name == root_name][-1]
    return root, sorted((s for s in spans if s.parent_id == root.span_id), key=lambda s: s.t0)


def assert_partition(root, leaves) -> None:
    assert leaves[0].t0 >= root.t0 and leaves[-1].t1 <= root.t1
    for a, b in zip(leaves, leaves[1:]):
        assert b.t0 >= a.t1, f"{a.name} and {b.name} overlap"
    assert sum(s.seconds for s in leaves) >= 0.98 * root.seconds


@pytest.mark.parametrize("backend,with_dict", CASES)
def test_pack_leaf_names_are_the_tables(work, backend, with_dict):
    if with_dict:
        dict_boot(work)
    trace.configure(enabled=True)
    pack(work, backend, with_dict=with_dict)
    root, leaves = tree("convert.pack")
    assert not root.parent_id and root.batch
    assert [s.name for s in leaves] == PACK_LEAVES[backend, with_dict]
    assert len(leaves) + 1 <= 24
    attrs = {}
    for s in leaves:  # the two pack:lane.pass1 leaves' attributes side by side
        attrs.setdefault(s.name, {}).update(s.attrs)
    assert attrs["pack:read"]["bytes"] == os.path.getsize(work / "a.tar")
    assert attrs["pack:scan"]["members"] == 20 and attrs["pack:scan"]["files_planned"] == 20
    assert attrs["pack:dedup"]["chunks"] >= attrs["pack:dedup"]["unique"] > 0
    assert attrs["pack:compress_write"]["blob_bytes"] > 0 and attrs["pack:bootstrap"]["inodes"] >= 20
    if with_dict:
        assert attrs["pack:dict_load"]["dict_chunks"] > 0 and attrs["pack:dict_load"]["dict_blobs"] == 1
    if backend == "fused":
        # the CLI read the tar into the lane's own buffer: nothing left to copy
        lay = attrs["pack:lane.layout"]
        assert lay["bytes"] == attrs["pack:read"]["bytes"] and lay["copied_bytes"] == 0
        assert lay["padded_bytes"] == attrs["pack:lane.h2d"]["bytes"] >= lay["bytes"] + 4 * CHUNK + 64
        plan = attrs["pack:lane.plan"]
        assert plan["blocks_padded"] == sum(cap * padded for cap, _rows, padded in plan["classes"])
        assert 0 < plan["blocks_real"] <= plan["blocks_padded"]
        assert sum(rows for _cap, rows, _padded in plan["classes"]) == attrs["pack:lane.digest_d2h"]["chunks"]
        assert attrs["pack:lane.pass2"]["programs_after"] >= attrs["pack:lane.pass2"]["programs_before"]
        # the first pack:lane.pass1 is the call, the second the wait: window_s runs from the
        # upload's enqueue to the counts on the host, covered_s is what of it the host spent
        # in pack:dict_load and pack:scan
        call, wait = [s for s in leaves if s.name == "pack:lane.pass1"]
        assert set(call.attrs) == {"wcap_s", "wcap_l", "join_compiles", *USAGE} and call.attrs["join_compiles"] == 0
        assert set(wait.attrs) == {"words_s", "words_l", "window_s", "covered_s", *USAGE}
        between = [s for s in leaves if call.t0 < s.t0 < wait.t0]
        assert [s.name for s in between] == ["pack:dict_load", "pack:scan"][not with_dict:]
        assert wait.attrs["covered_s"] == pytest.approx(sum(s.seconds for s in between), abs=1e-6)
        h2d = next(s for s in leaves if s.name == "pack:lane.h2d")
        assert 0 < wait.attrs["covered_s"] <= wait.attrs["window_s"] <= wait.t1 - h2d.t0
        assert wait.attrs["window_s"] >= wait.t0 - h2d.t0


@pytest.mark.parametrize("backend,with_dict", CASES)
def test_pack_leaves_partition_the_root(work, backend, with_dict):
    # warm (imports, jit) first: the partition is judged on a steady pack
    pack(work, backend, "many", with_dict)
    pack(work, backend, "many", with_dict)
    assert_partition(*tree("convert.pack"))


@pytest.mark.parametrize("backend", ["fused", "hybrid"])
def test_span_count_does_not_follow_the_file_count(work, backend):
    counts = []
    for name in ("a", "many"):
        trace.configure(enabled=True)
        pack(work, backend, name)
        counts.append(len(trace.snapshot_spans()))
    assert counts[0] == counts[1] == len(PACK_LEAVES[backend, False]) + 1


def test_merge_leaves(work):
    layers = [pack(work, "hybrid", "many"), pack(work, "hybrid", "b")]
    run_cli("merge", "--out", str(work / "ab.boot"), *layers)  # warm: the partition is judged on the second
    trace.configure(enabled=True)
    run_cli("merge", "--out", str(work / "ab.boot"), *layers)
    root, leaves = tree("convert.merge")
    assert [s.name for s in leaves] == MERGE_LEAVES and len(leaves) + 1 <= 8
    assert root.batch and not root.parent_id
    assert {k: v for k, v in leaves[0].attrs.items() if k not in USAGE} == {
        "layers": 2, "bytes_read": sum(os.path.getsize(p) for p in layers)}
    assert leaves[1].attrs["layers"] == 2 and leaves[2].attrs["inodes"] > 2000
    assert_partition(root, leaves)


@pytest.mark.skipif(trace._RUSAGE_THREAD is None, reason="no RUSAGE_THREAD on this platform")
@pytest.mark.parametrize("backend,with_dict", CASES)
def test_every_leaf_reads_its_threads_usage_and_no_other_span_does(work, backend, with_dict):
    if with_dict:
        dict_boot(work)
    trace.configure(enabled=True)
    blob = pack(work, backend, with_dict=with_dict)
    run_cli("merge", "--out", str(work / f"usage.{backend}.boot"), blob)
    spans = trace.snapshot_spans()
    roots = {s.span_id for s in spans if s.name in ("convert.pack", "convert.merge")}
    leaves = [s for s in spans if s.parent_id in roots]
    assert {s.name for s in leaves} == set(PACK_LEAVES[backend, with_dict]) | set(MERGE_LEAVES)
    for s in spans:  # the roots carry none, and neither would a worker's span under a leaf
        assert USAGE & set(s.attrs) == (USAGE if s.parent_id in roots else set()), s.name
    for s in leaves:
        a = s.attrs
        assert 0 <= a["cpu_s"] <= s.seconds + 0.005 and a["waits"] >= 0 and a["preempts"] >= 0, (s.name, a)
        assert 0 <= a["gc_s"] <= s.seconds, (s.name, a)


@pytest.mark.parametrize("verb", ["Pack", "Merge"])
def test_library_entry_opens_the_root_itself(work, verb):
    """Without the CLI around it the library entry is the root: no leaf is a root of its own."""
    blob = io.BytesIO()
    Pack(blob, (work / "a.tar").read_bytes(), PackOption(backend="hybrid", chunk_size=CHUNK))
    if verb == "Merge":
        trace.configure(enabled=True)
        Merge([blob.getvalue()], MergeOption())
    roots = [s for s in trace.snapshot_spans() if not s.parent_id]
    assert [s.name for s in roots] == [f"convert.{verb.lower()}"]


def stage_counters() -> dict:
    stages = fused_convert._counters()[2]
    return {s: stages.value(s) for s in ("layout", "h2d", "pass1_gear", "host_resolve", "pass2_digest", "digest_d2h")}


@pytest.mark.parametrize("enabled", [True, False])
def test_stage_counters_are_the_spans_own_seconds(work, enabled):
    """One pair of clock reads a boundary: the counters' deltas ARE the
    spans' durations (and are still fed with the tracer off)."""
    pack(work, "fused")  # warm
    trace.configure(enabled=enabled)
    before = stage_counters()
    pack(work, "fused")
    delta = {k: v - before[k] for k, v in stage_counters().items()}
    assert all(v > 0 for v in delta.values())
    if not enabled:
        assert trace.snapshot_spans() == []
        return
    took = {}
    for s in tree("convert.pack")[1]:  # pass1 is two leaves, the call and the wait
        took[s.name.removeprefix("pack:lane.")] = took.get(s.name.removeprefix("pack:lane."), 0.0) + s.seconds
    want = {"layout": took["layout"], "h2d": took["h2d"], "pass1_gear": took["pass1"] + took["cand_d2h"],
            "host_resolve": took["resolve"] + took["plan"], "pass2_digest": took["pass2"],
            "digest_d2h": took["digest_d2h"]}
    assert delta == pytest.approx(want, abs=1e-6)


@pytest.mark.parametrize("backend,with_dict", CASES)
def test_early_start_counter_rises_by_one_a_fused_pack(work, backend, with_dict):
    """Every served fused pack begins its lane before the dictionary and the
    scan and finishes it: one early start a dispatch, none on the host lanes."""
    if with_dict:
        dict_boot(work)
    early, dispatches = fused_convert._early_start_counter(), fused_convert._counters()[0]
    before = early.value(), dispatches.value()
    pack(work, backend, with_dict=with_dict)
    pack(work, backend, "many", with_dict)
    want = 2 if backend == "fused" else 0
    assert (early.value() - before[0], dispatches.value() - before[1]) == (want, want)


def test_plan_span_counts_the_row_floor_and_the_rows_dispatched(work, monkeypatch):
    """`pack:lane.plan` says what pass 2 is given: `blocks_padded` is the
    rows x capacity of the buckets handed to `digest_probe`, padding rows
    of the floor included, and `row_floor_*` say how many of them the
    floor added."""
    dispatched = []
    digest_probe = fused_convert.FusedDeviceEngine.digest_probe

    def spy(self, buffer_dev, buckets, *args, **kw):
        dispatched.append([(b.cap_blocks, b.count, len(b.offsets)) for b in buckets])
        return digest_probe(self, buffer_dev, buckets, *args, **kw)

    monkeypatch.setattr(fused_convert.FusedDeviceEngine, "digest_probe", spy)
    trace.configure(enabled=True)
    pack(work, "fused")
    plan = {s.name: s.attrs for s in tree("convert.pack")[1]}["pack:lane.plan"]
    (buckets,) = dispatched
    assert [list(b) for b in buckets] == plan["classes"]
    assert plan["blocks_padded"] == sum(cap * rows for cap, _live, rows in buckets)
    added = [rows - fused_convert._pow2_ceil(live) for _cap, live, rows in buckets]
    assert all(rows == fused_convert.bucket_rows(live) for _cap, live, rows in buckets)
    assert plan["row_floor_rows"] == sum(added) > 0  # a.tar's plan has a one-row class
    assert plan["row_floor_classes"] == sum(1 for a in added if a) > 0


@pytest.mark.parametrize("name,files,big", [("a", 20, 4), ("many", 2000, 4)])
def test_resolve_span_counts_the_files_of_one_chunk(work, name, files, big):
    """`pack:lane.resolve` tells the files of one chunk (no longer than the
    chunker's min_size: no candidate judged) from the ones CDC cuts."""
    trace.configure(enabled=True)
    pack(work, "fused", name)
    resolve = {s.name: s.attrs for s in tree("convert.pack")[1]}["pack:lane.resolve"]
    assert resolve["files"] == files and resolve["single_chunk_files"] == files - big  # make_tar: 100-3,000 B
    assert resolve["chunks"] >= resolve["single_chunk_files"] + big
    assert len(fused_convert._counters()) == 4  # benchmark/program.py and chip_smoke.py unpack four


def long_name_tar() -> bytes:
    """a.tar's shape with a GNU long name in it: the fast member walk gives
    up on the 'L' header and tarfile walks the layer."""
    rng = np.random.default_rng(5)
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w", format=tarfile.GNU_FORMAT) as tf:
        for i, size in enumerate([300_000, 2_000, 450_000]):
            info = tarfile.TarInfo(f"d/{'n' * 120 if i == 1 else 'f'}{i}")
            info.size = size
            tf.addfile(info, io.BytesIO(rng.integers(0, 256, size, dtype=np.uint8).tobytes()))
    return buf.getvalue()


@pytest.mark.parametrize("with_dict", [False, True])
@pytest.mark.parametrize("source", ["cli", "bytes", "bytearray", "array", "array-with-room", "tarfile-walk"])
def test_layout_copies_only_a_tar_with_no_room_behind_it(work, source, with_dict):
    """`pack:lane.layout` says what the lane had to copy to get its padded
    buffer: nothing where the tar came as the head of an array with the
    room (the served CLI; tarfile's walk or the fast one), the whole tar
    where it came as bytes. Same blob as the host lane either way."""
    tar = long_name_tar() if source == "tarfile-walk" else (work / "a.tar").read_bytes()
    extra = {"chunk_dict_path": dict_boot(work)} if with_dict else {}
    want = io.BytesIO()
    Pack(want, tar, PackOption(backend="hybrid", chunk_size=CHUNK, **extra))
    trace.configure(enabled=True)
    if source == "cli":
        with open(pack(work, "fused", with_dict=with_dict), "rb") as f:
            got = f.read()
    else:
        if source in ("array-with-room", "tarfile-walk"):
            big = fused_convert.zeroed_buffer(fused_convert.padded_length(len(tar), 4 * CHUNK))
            big[: len(tar)] = np.frombuffer(tar, dtype=np.uint8)
            src = big[: len(tar)]
        else:
            src = {"bytes": tar, "bytearray": bytearray(tar), "array": np.frombuffer(tar, dtype=np.uint8)}[source]
        out = io.BytesIO()
        Pack(out, src, PackOption(backend="fused", chunk_size=CHUNK, **extra))
        got = out.getvalue()
    assert got == want.getvalue()
    lay = {s.name: s.attrs for s in tree("convert.pack")[1]}["pack:lane.layout"]
    roomy = source in ("cli", "array-with-room", "tarfile-walk")
    assert lay["copied_bytes"] == (0 if roomy else len(tar))
    assert lay["bytes"] == len(tar)
    assert lay["padded_bytes"] == fused_convert.padded_length(len(tar), 4 * CHUNK)


@pytest.mark.parametrize("reshape", [lambda t: t.view(np.uint16), lambda t: t.reshape(2, -1), lambda t: t[::2]],
                         ids=["uint16", "2-D", "strided"])
def test_a_tar_array_of_another_shape_is_refused(reshape):
    from nydus_snapshotter_tpu.converter.types import ConvertError

    bad = reshape(np.frombuffer(long_name_tar(), dtype=np.uint8))
    with pytest.raises(ConvertError, match="contiguous 1-D uint8"):
        Pack(io.BytesIO(), bad, PackOption(backend="hybrid", chunk_size=CHUNK))


@pytest.mark.parametrize("backend,with_dict", CASES)
def test_stats_hold_the_spans_sums(work, backend, with_dict):
    from nydus_snapshotter_tpu.converter.stream import _STATS_SPANS

    opt = PackOption(backend=backend, chunk_size=CHUNK, chunk_dict_path=dict_boot(work) if with_dict else "")
    trace.configure(enabled=True)
    stats = {}
    Pack(io.BytesIO(), (work / "a.tar").read_bytes(), opt, stats=stats)
    _root, leaves = tree("convert.pack")
    for key, prefixes in _STATS_SPANS.items():
        assert stats[key] == pytest.approx(sum(s.seconds for s in leaves if s.name.startswith(prefixes)), abs=1e-6)
    assert sum(stats.values()) == pytest.approx(sum(s.seconds for s in leaves), abs=1e-6)
    assert stats["chunk_digest"] + stats["fused_pack"] > 0 and (stats["dict_load"] > 0) == with_dict


@pytest.mark.parametrize("backend,with_dict", CASES)
def test_artifacts_are_byte_identical_with_tracing_off(work, backend, with_dict):
    def convert(name: str):
        blob = pack(work, backend, "a", with_dict)
        boot = str(work / f"{name}.boot")
        run_cli("merge", "--out", boot, blob)
        with open(blob, "rb") as f, open(boot, "rb") as g:
            return f.read(), g.read()

    traced = convert("on")
    trace.configure(enabled=False)
    assert convert("off") == traced
    assert trace.snapshot_spans() == []


def test_convert_roots_never_fire_the_slow_op_recorder(work, caplog):
    trace.configure(enabled=True, slow_op_threshold_ms=0.001)  # every other root is "slow"
    blob = pack(work, "fused")
    run_cli("merge", "--out", str(work / "slow.boot"), blob)
    Pack(io.BytesIO(), (work / "a.tar").read_bytes(), PackOption(backend="hybrid", chunk_size=CHUNK))
    assert trace.slow_ops() == [] and "slow op" not in caplog.text
    with trace.span("grpc.Prepare"):
        pass
    assert [r["op"] for r in trace.slow_ops()] == ["grpc.Prepare"]


def test_batch_span_is_reentrant_by_name_and_a_child_under_convert():
    with trace.span("convert", image="x"):
        with trace.batch_span("convert.pack"):
            with trace.batch_span("convert.pack"):
                pass
    spans = {s.name: s for s in trace.snapshot_spans()}
    assert len(trace.snapshot_spans()) == 2
    assert spans["convert.pack"].parent_id == spans["convert"].span_id


def test_stages_close_the_running_stage_on_error():
    with pytest.raises(ValueError):
        with trace.span("root"), trace.Stages() as stages:
            stages.next("a")
            stages.next("b", n=1)
            raise ValueError("mid-stage")
    assert [s.name for s in trace.snapshot_spans()] == ["root", "a", "b"]
    assert set(stages.seconds) == {"a", "b"} and trace.capture() is None


class Recorded:
    """Stands for jax.profiler.TraceAnnotation."""

    events: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.events.append(("enter", self.name))

    def __exit__(self, *exc):
        self.events.append(("exit", self.name))


def test_profiler_bridge_sees_every_span(work):
    Recorded.events = []
    trace.install_profiler_bridge(Recorded)
    pack(work, "hybrid")  # the host lane installs nothing itself
    names = [s.name for s in sorted(trace.snapshot_spans(), key=lambda s: s.t0)]
    assert [n for kind, n in Recorded.events if kind == "enter"] == names
    assert Recorded.events[0] == ("enter", "convert.pack") and Recorded.events[-1] == ("exit", "convert.pack")


def test_device_backend_installs_the_real_bridge_and_no_session_is_fine(work):
    import jax

    assert trace._profiler_annotation is None
    pack(work, "fused")
    assert trace._profiler_annotation is jax.profiler.TraceAnnotation
    assert [s.name for s in tree("convert.pack")[1]] == PACK_LEAVES["fused", False]


def test_trace_does_not_import_jax():
    code = ("import sys; import nydus_snapshotter_tpu.trace as t; t.install_profiler_bridge(None); "
            "sp = t.stage('x'); sp.__enter__(); sp.end(); assert 'jax' not in sys.modules, 'jax imported'")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    subprocess.run([sys.executable, "-c", code], check=True, cwd=root)


def test_cli_result_lines_carry_no_timing(work, capsys):
    blob = pack(work, "fused")
    run_cli("merge", "--out", str(work / "line.boot"), blob)
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()[-2:]]
    assert [sorted(l) for l in lines] == [["blob_id", "blob_size", "referenced_blobs"], ["blob_digests"]]
