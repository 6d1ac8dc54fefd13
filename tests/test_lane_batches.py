"""A layer that no one lane buffer holds is packed on the device as batches
of whole files (ops/fused_convert.plan_batches / process_batches,
converter/stream._lane_device): same cuts, digests and artifacts as one
batch and as the host lanes, every byte of the tar uploaded exactly once,
no host copy, the same plans whatever the order of the members, and a
layer that fits untouched.

CPU backend, a few MiB: the int32 limit of a lane buffer
(fused_convert.ADDRESS_LIMIT) is patched down to 8 MiB, so that a lane
buffer is 4 MiB (one WINDOW), a file of 2 MiB or more is a batch of its
own, and the split engages at sizes a test can afford.
"""

import contextlib
import filecmp
import io
import json
import tarfile
import threading

import numpy as np
import pytest

from nydus_snapshotter_tpu import trace
from nydus_snapshotter_tpu.cmd import convert as cli
from nydus_snapshotter_tpu.ops import cdc, fused_convert
from nydus_snapshotter_tpu.ops.chunker import ChunkDigestEngine

CHUNK = 0x1000
MAX_SIZE = cdc.CDCParams(CHUNK).max_size
LIMIT = 8 << 20  # a lane buffer pads to 4 MiB or not at all
BIG = 2_600_000  # over LIMIT // 4: a batch of its own


def members(n_small: int, small_bytes: int, big: int = BIG, seed: int = 36) -> list[tuple[str, bytes]]:
    """One big file and ``n_small`` others of ``small_bytes`` in all, a
    third of them long enough for CDC to cut; contents follow the name."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 3000, n_small)
    sizes[::3] = rng.integers(20_000, 90_000, len(sizes[::3]))
    sizes = (sizes * (small_bytes / sizes.sum())).astype(int) + 1
    out = [(f"d{i % 5}/f{i}", rng.integers(0, 256, int(s), dtype=np.uint8).tobytes()) for i, s in enumerate(sizes)]
    if big:
        out.insert(n_small // 2, ("lib/big.so", rng.integers(0, 256, big, dtype=np.uint8).tobytes()))
    return out


def tar_of(files: list[tuple[str, bytes]], order=None) -> bytes:
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w", format=tarfile.GNU_FORMAT) as tf:
        for i in order if order is not None else range(len(files)):
            name, data = files[i]
            info = tarfile.TarInfo(name)
            info.size = len(data)
            tf.addfile(info, io.BytesIO(data))
    return buf.getvalue()


def extents_of(tar: bytes) -> list[tuple[int, int]]:
    with tarfile.open(fileobj=io.BytesIO(tar)) as tf:
        return [(m.offset_data, m.size) for m in tf.getmembers() if m.size]


@pytest.fixture(autouse=True)
def small_limit(monkeypatch):
    monkeypatch.setattr(fused_convert, "ADDRESS_LIMIT", LIMIT)
    monkeypatch.setenv("NTPU_PACK_THREADS", "1")
    trace.configure(enabled=True)
    yield
    trace.reset()


def counters() -> dict:
    disp, by_bytes, stages, fallbacks = fused_convert._counters()
    return {
        "dispatches": disp.value(), "bytes": by_bytes.value(), "host_fallbacks": fallbacks.value(),
        "split_packs": fused_convert._split_packs_counter().value(),
        "early_starts": fused_convert._early_start_counter().value(),
        # what the layout stage copied, by the spans of the test's own ring
        "copied": sum(s.attrs.get("copied_bytes", 0) for s in trace.snapshot_spans()
                      if s.name == "pack:lane.layout"),
        "stage_seconds": sum(stages.value(s) for s in
                             ("layout", "h2d", "pass1_gear", "host_resolve", "pass2_digest", "digest_d2h")),
    }


def rise(before: dict) -> dict:
    return {k: v - before[k] for k, v in counters().items()}


def pack(tmp_path, tar: bytes, backend: str, name: str = "layer", line: bool = True) -> tuple[str, dict]:
    """`cmd.convert pack` as the served CLI runs it -> (the blob's path, its
    result line; None where ``line`` is off: redirect_stdout swaps the
    process's one sys.stdout, so two threads at once leave it alone)."""
    src, out = tmp_path / f"{name}.tar", tmp_path / f"{name}.{backend}.nydus"
    src.write_bytes(tar)
    argv = ["--jax-platform", "cpu", "pack", "--in", str(src), "--out", str(out), "--backend", backend,
            "--chunk-size", hex(CHUNK)]
    if not line:
        assert cli.main(argv) == 0
        return str(out), None
    stdout = io.StringIO()
    with contextlib.redirect_stdout(stdout):
        assert cli.main(argv) == 0
    return str(out), json.loads(stdout.getvalue().strip().splitlines()[-1])


def lane_leaves(name: str) -> list:
    """The last pack's ``pack:lane.<name>`` leaves, in start order."""
    spans = trace.snapshot_spans()
    root = [s for s in spans if s.name == "convert.pack"][-1]
    return sorted((s for s in spans if s.parent_id == root.span_id and s.name == f"pack:lane.{name}"),
                  key=lambda s: s.t0)


def join_compiles() -> list[tuple[int, int]]:
    """The last pack's (u8, u32) ``join_compiles`` a batch: ``pack:lane.h2d``'s and the pass-1 call's."""
    calls = [s for s in lane_leaves("pass1") if "wcap_s" in s.attrs]
    return [(h.attrs["join_compiles"], c.attrs["join_compiles"]) for h, c in zip(lane_leaves("h2d"), calls)]


# -- the plan ---------------------------------------------------------------------


def table_of(sizes: list[int], gap: int = 512) -> tuple[list[tuple[int, int]], int]:
    """Files of ``sizes`` laid out as a tar lays them: a header before, padding after."""
    pos, table = 0, []
    for size in sizes:
        table.append((pos + gap, size))
        pos += gap + -(-size // 512) * 512
    return table, pos + 1024


def test_a_big_file_is_a_batch_of_its_own_and_the_others_fill_batches_in_order():
    cap = (4 << 20) - MAX_SIZE - 64  # what a 4 MiB buffer holds
    table, size = table_of([1_000_000, 900_000, BIG, 1_200_000, 1_100_000, 2_097_152, 700_000])
    plan = fused_convert.plan_batches(table, size, MAX_SIZE)
    assert [b.files for b in plan] == [(0, 1, 3), (4, 6), (2,), (5,)]
    assert all(b.size <= cap for b in plan)
    # the runs partition the buffer: every header, padding and end block in exactly one batch
    runs = sorted(r for b in plan for r in b.runs)
    assert runs[0][0] == 0 and sum(runs[-1]) == size
    assert all(sum(a) == b[0] for a, b in zip(runs, runs[1:]))
    assert sum(b.size for b in plan) == size
    # a file's stretch runs from its first byte to the next file's: the seam is where a file starts
    assert plan[2].runs == ((table[2][0], table[3][0] - table[2][0]),)
    assert plan[0].runs == ((0, table[2][0]), (table[3][0], table[4][0] - table[3][0]))


def test_one_file_past_the_limit_is_refused_with_its_size():
    table, size = table_of([100_000, 5_000_000, 100_000])
    with pytest.raises(fused_convert.FusedOverflow, match="one file of 5000000 bytes"):
        fused_convert.plan_batches(table, size, MAX_SIZE)


def test_a_seam_off_a_word_boundary_is_refused():
    with pytest.raises(fused_convert.FusedOverflow, match="word"):
        fused_convert.plan_batches([(0, 10), (4_000_001, 3_000_000)], 7_100_000, MAX_SIZE)


@pytest.mark.parametrize("runs,table,error", [
    (((0, 1024), (512, 1024)), [(0, 8)], "ascending"),
    (((0, 1022),), [(0, 8)], "whole words"),
    (((0, 1 << 20),), [(0, 8)], "ascending"),  # past the buffer's end
    (((0, 1024), (2048, 1024)), [(1020, 8)], "lies in no run"),
    (((0, 1024), (2048, 1024)), [(1500, 8)], "lies in no run"),
])
def test_runs_that_are_not_a_batch_of_the_buffer_are_refused(runs, table, error):
    eng = fused_convert.FusedDeviceEngine(chunk_size=CHUNK)
    with pytest.raises(ValueError, match=error):
        eng._lay(fused_convert.Extents(np.zeros(4096, np.uint8), table, runs))


# -- the engine ---------------------------------------------------------------------


@pytest.fixture(scope="module")
def layer2():
    files = members(40, 3_000_000)
    return files, tar_of(files)


def test_the_split_lane_equals_the_numpy_reference_and_the_unsplit_lane(layer2, monkeypatch):
    _files, tar = layer2
    table = extents_of(tar)
    arr = np.frombuffer(tar, np.uint8)
    eng = fused_convert.FusedDeviceEngine(chunk_size=CHUNK)
    before = counters()
    split = eng.process_batches(fused_convert.Extents(arr, table))
    up = rise(before)
    assert (up["dispatches"], up["bytes"], up["split_packs"], up["copied"]) == (2, len(tar), 1, 0)
    monkeypatch.setattr(fused_convert, "ADDRESS_LIMIT", 1 << 31)
    whole = eng.process_batches(fused_convert.Extents(arr, table))  # it fits: process_many's own one batch
    up = rise(before)
    assert (up["dispatches"], up["bytes"], up["split_packs"]) == (3, 2 * len(tar), 1)
    want = ChunkDigestEngine(chunk_size=CHUNK, backend="numpy", digest_backend="numpy").process_many(
        [arr[off:off + length] for off, length in table])
    assert len(split.cuts) == len(whole.cuts) == len(table)
    for got, one, metas in zip(zip(split.cuts, split.digests), zip(whole.cuts, whole.digests), want):
        assert list(map(int, got[0])) == list(map(int, one[0])) == [m.offset + m.size for m in metas]
        assert got[1] == one[1] == [m.digest for m in metas]
    assert max(len(c) for c in split.cuts) > 100  # the big file was cut, and in its own batch


def test_the_probe_of_a_split_layer_is_in_the_tables_order(layer2):
    from nydus_snapshotter_tpu.parallel.sharded_dict import _build_host_tables, _table_max_depth

    _files, tar = layer2
    table, arr = extents_of(tar), np.frombuffer(tar, np.uint8)
    eng = fused_convert.FusedDeviceEngine(chunk_size=CHUNK)
    plain = eng.process_batches(fused_convert.Extents(arr, table))
    flat = [d for f in plain.digests for d in f]
    held = flat[::3]
    keys, values = _build_host_tables(np.frombuffer(b"".join(held), dtype=">u4").astype(np.uint32).reshape(-1, 8), 1)
    res = eng.process_batches(
        fused_convert.Extents(arr, table), chunk_dict=(keys[0], values[0]), depth=_table_max_depth(keys, values)
    )
    assert res.digests == plain.digests and len(res.probe) == len(flat)
    assert [int(p) > 0 for p in res.probe] == [d in set(held) for d in flat]
    assert all(held[int(p) - 1] == d for p, d in zip(res.probe, flat) if p > 0)


# -- the pack -------------------------------------------------------------------------


@pytest.mark.parametrize("batches,n_small,small_bytes", [(2, 40, 3_000_000), (3, 60, 5_200_000)])
def test_a_split_pack_is_the_hybrid_packs_bytes(tmp_path, batches, n_small, small_bytes):
    tar = tar_of(members(n_small, small_bytes))
    want_path, want_line = pack(tmp_path, tar, "hybrid")
    before = counters()
    got_path, got_line = pack(tmp_path, tar, "fused")
    up = rise(before)
    assert got_line == want_line
    assert filecmp.cmp(got_path, want_path, shallow=False)  # blob, bootstrap and TOC: one file
    # the partition: a dispatch a batch, every byte of the tar in exactly one, no host copy
    assert (up["dispatches"], up["bytes"], up["split_packs"]) == (batches, len(tar), 1)
    # no early start: the batches were begun after the scan, which gave their files
    assert (up["copied"], up["host_fallbacks"], up["early_starts"]) == (0, 0, 0)
    layouts = [s.attrs for s in lane_leaves("layout")]
    assert [(a["batch"], a["batches"]) for a in layouts] == [(k + 1, batches) for k in range(batches)]
    assert sum(a["bytes"] for a in layouts) == len(tar) and all(a["copied_bytes"] == 0 for a in layouts)
    assert [a["runs"] for a in layouts] == [2, *[1] * (batches - 1)]  # the others lie around the big file
    assert all(a["padded_bytes"] == 4 << 20 for a in layouts)
    assert sum(s.attrs["bytes"] for s in lane_leaves("h2d")) == len(tar)  # the padding is made on the device
    # a batch's runs are joined and padded by a program of their lengths, as bytes under
    # lane.h2d and as words under the pass-1 call: the leaves say when one compiled
    assert all(pair in ((0, 0), (1, 1)) for pair in join_compiles())
    # batch k+1 is begun before batch k's candidates are waited for
    starts = {name: [s.t0 for s in lane_leaves(name)] for name in ("layout", "cand_d2h")}
    assert all(starts["layout"][k + 1] < starts["cand_d2h"][k] for k in range(batches - 1))
    # the stage counters are the lane leaves' own seconds, none twice
    spans = trace.snapshot_spans()
    root = [s for s in spans if s.name == "convert.pack"][-1]
    lane_s = sum(s.seconds for s in spans if s.parent_id == root.span_id and s.name.startswith("pack:lane."))
    assert up["stage_seconds"] == pytest.approx(lane_s, rel=1e-6)
    # a later batch's front was covered by its sibling's lane leaves, inside its window
    waits = [s.attrs for s in lane_leaves("pass1") if "window_s" in s.attrs]
    assert len(waits) == batches and waits[0]["covered_s"] == 0  # begun after the scan: the scan is no cover
    assert all(0 < a["covered_s"] <= a["window_s"] for a in waits[1:])


    # the same tar again: the process kept the join programs, nothing compiles
    pack(tmp_path, tar, "fused", "again")
    assert join_compiles() == [(0, 0)] * batches


def test_two_orders_of_the_same_members_give_the_same_plans(tmp_path):
    files = members(40, 3_000_000)
    rng = np.random.default_rng(7)
    plans, blobs = [], []
    for k in range(2):
        path, _line = pack(tmp_path, tar_of(files, rng.permutation(len(files))), "fused", f"order{k}")
        plans.append([s.attrs["classes"] for s in lane_leaves("plan")])
        blobs.append(path)
    assert len(plans[0]) == 2 and plans[0] == plans[1]  # so _pass2 compiled once a batch, not once an order
    assert not filecmp.cmp(*blobs, shallow=False)  # another tar order is another blob


def test_a_layer_that_fits_is_one_batch_begun_when_it_is_read(tmp_path):
    tar = tar_of(members(30, 1_500_000, big=0))
    want_path, want_line = pack(tmp_path, tar, "hybrid")
    before = counters()
    got_path, got_line = pack(tmp_path, tar, "fused")
    up = rise(before)
    assert got_line == want_line and filecmp.cmp(got_path, want_path, shallow=False)
    assert (up["dispatches"], up["bytes"], up["early_starts"], up["split_packs"], up["copied"]) == (1, len(tar), 1, 0, 0)
    (layout,) = lane_leaves("layout")
    assert (layout.attrs["batch"], layout.attrs["batches"], layout.attrs["runs"]) == (1, 1, 1)
    assert layout.attrs["bytes"] == len(tar) and layout.attrs["padded_bytes"] == 4 << 20
    spans = trace.snapshot_spans()
    scan = [s for s in spans if s.name == "pack:scan"][-1]
    assert layout.t0 < scan.t0  # the early start: before the tar is walked


def test_read_layer_keeps_a_layer_past_one_buffer_as_an_array_on_a_page_boundary(tmp_path):
    from nydus_snapshotter_tpu.converter import stream
    from nydus_snapshotter_tpu.converter.types import PackOption

    tar = tar_of(members(40, 3_000_000))
    (tmp_path / "l.tar").write_bytes(tar)
    with open(tmp_path / "l.tar", "rb") as f:
        src = stream.read_layer(f, PackOption(backend="fused", chunk_size=CHUNK))
    assert isinstance(src, np.ndarray) and src.size == len(tar) and bytes(src) == tar
    assert src.ctypes.data % 4096 == 0  # its runs go up as views of it: no second host copy


def test_one_file_over_the_limit_declines_loudly_and_the_host_lanes_pack_it(tmp_path):
    tar = tar_of(members(10, 200_000, big=5_000_000))
    want_path, want_line = pack(tmp_path, tar, "hybrid")
    before = counters()
    got_path, got_line = pack(tmp_path, tar, "fused")
    up = rise(before)
    assert got_line == want_line and filecmp.cmp(got_path, want_path, shallow=False)
    assert (up["host_fallbacks"], up["dispatches"], up["split_packs"]) == (1, 0, 0)
    assert not lane_leaves("layout")  # nothing was begun for it


def test_two_split_packs_from_two_threads_at_once_leave_the_serial_packs_bytes(tmp_path):
    """The tier-1 copy of benchmark/tests/test_callers.py's two-thread test
    (PR 35), on layers that split: after a serial warm-up (a first call
    compiles: never two at once), two packs at once, three rounds."""
    tars = [tar_of(members(40, 3_000_000, seed=s)) for s in (36, 37)]
    serial = []
    for k, tar in enumerate(tars):
        (tmp_path / f"serial{k}").mkdir()
        serial.append(pack(tmp_path / f"serial{k}", tar, "fused"))
    before = counters()
    for round_ in range(3):
        got = [None, None]

        def send(k: int) -> None:
            d = tmp_path / f"round{round_}.{k}"
            d.mkdir()
            got[k] = pack(d, tars[k], "fused", line=False)

        threads = [threading.Thread(target=send, args=(k,)) for k in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for (path, _line), (want_path, _want_line) in zip(got, serial):
            assert filecmp.cmp(path, want_path, shallow=False)  # the blob's id and size are in it
    up = rise(before)
    assert (up["dispatches"], up["bytes"], up["split_packs"]) == (12, 3 * sum(map(len, tars)), 6)
    assert (up["host_fallbacks"], up["copied"]) == (0, 0)
