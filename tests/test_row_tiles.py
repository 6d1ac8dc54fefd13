"""Row tiles in the pass-2 plan (ops/fused_convert.class_rows): a digest
class whose batch would exceed TILE_BYTES is gathered and digested in tiles
of a power-of-two row count, by one loop body, and gives the very cuts,
digests and probe hits of the one batch; a class within the budget keeps the
plan it had before there were tiles (bucket_rows).

CPU backend, small sizes, the budget monkeypatched down so that a class of a
few rows (a served batch of 2-8 MiB) tiles. The layer that needs tiles at the
real budget is benchmark/configs/mlimage-1m.json's; tests/test_chip_compile.py
holds its widest class to the chip's compiler.
"""

import hashlib

import numpy as np
import pytest

from benchmark import reference
from benchmark.traffic import image
from nydus_snapshotter_tpu import trace
from nydus_snapshotter_tpu.converter.convert import bootstrap_from_layer_blob
from nydus_snapshotter_tpu.ops import fused_convert
from nydus_snapshotter_tpu.parallel.sharded_dict import _build_host_tables, _table_max_depth
from tests.test_fused_convert import SMALL, _thin_top_batch
from tests.test_smallfiles_convert import run_cli

R = fused_convert.ROW_FLOOR
MIB = 1 << 20


# (rows of the top class that fit the budget, its chunks) -> the tiles it has to run in
TOP_CLASS = {
    "exactly_one_tile": (4, 4, 1),
    "two_full_tiles": (4, 8, 2),
    "three_tiles_the_last_nearly_all_padding": (4, 9, 3),
    "a_tiled_class_beside_one_that_is_not": (8, 17, 3),
    "tiles_of_the_row_floor": (R, 2 * R + 1, 3),
}


@pytest.mark.parametrize("digester", ["sha256", "blake3"])
@pytest.mark.parametrize("case", list(TOP_CLASS))
def test_tiled_classes_give_the_one_batchs_cuts_digests_and_probe(monkeypatch, digester, case):
    fit_rows, top_rows, tiles = TOP_CLASS[case]
    streams = _thin_top_batch(top_rows)  # its top class holds exactly top_rows max-size chunks
    eng = fused_convert.FusedDeviceEngine(chunk_size=SMALL, digester=digester)
    whole = eng.process_many(streams)
    flat = [d for digs in whole.digests for d in digs]
    # a dictionary of the batch's own chunks: every probe row has to name its
    # own chunk, so a row base shifted by a tile's padding rows shows
    words = "<u4" if digester == "blake3" else ">u4"
    keys, values = _build_host_tables(np.frombuffer(b"".join(flat), dtype=words).astype(np.uint32).reshape(-1, 8), 1)
    table = (keys[0], values[0])
    depth = _table_max_depth(keys, values)
    whole = eng.process_many(streams, chunk_dict=table, depth=depth)

    monkeypatch.setattr(fused_convert, "TILE_BYTES", fit_rows * eng.max_read_span())
    trace.configure(enabled=True)
    try:
        tiled = eng.process_many(streams, chunk_dict=table, depth=depth)
        (plan,) = [s.attrs for s in trace.snapshot_spans() if s.name == "pack:lane.plan"]
    finally:
        trace.reset()
    for i, (got, want) in enumerate(zip(tiled.cuts, whole.cuts)):
        np.testing.assert_array_equal(got, want, err_msg=f"stream {i}")
    assert tiled.digests == whole.digests
    np.testing.assert_array_equal(tiled.probe, whole.probe)
    assert all(hit > 0 and flat[int(hit) - 1] == d for d, hit in zip(flat, tiled.probe))
    if digester == "sha256":
        run = streams[2]
        size = eng.params.max_size
        assert tiled.digests[2] == [hashlib.sha256(run[i : i + size]).digest() for i in range(0, len(run), size)]

    offsets = np.cumsum([0] + [len(s) for s in streams])
    buckets, order = eng.plan_buckets([(int(o), len(s)) for o, s in zip(offsets, streams)], tiled.cuts)
    top = buckets[-1]
    assert (top.count, top.tiles) == (top_rows, tiles)
    assert len(top.offsets) == tiles * top.tile_rows < 2 * fused_convert._pow2_ceil(top_rows)
    assert top.tile_rows == (fit_rows if tiles > 1 else fused_convert.bucket_rows(top_rows))
    assert not top.sizes[top.count :].any()  # padding rows only behind the last live one
    assert all(row < b.count for b in buckets for cap, row in order if cap == b.cap_blocks)
    assert plan["row_tiles"] == sum(b.tiles - 1 for b in buckets)
    if case == "a_tiled_class_beside_one_that_is_not":
        assert {b.tiles > 1 for b in buckets} == {True, False}


@pytest.mark.parametrize("digester", ["sha256", "blake3"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_plan_under_the_budget_is_the_row_rules_bucket_for_bucket(digester, seed):
    """Host only, at the real budget: every class of a drawn batch (the
    chunk sizes log-uniform, as a layer's long classes are thin) is one batch
    of bucket_rows(count) rows, as it was before there were tiles."""
    rng = np.random.default_rng(500 + seed)
    eng = fused_convert.FusedDeviceEngine(chunk_size=0x10000, digester=digester)
    table, cuts, total = [], [], 0
    for _ in range(200):
        sizes = np.exp(rng.uniform(0, np.log(eng.params.max_size), int(rng.integers(1, 12)))).astype(np.int64)
        table.append((total, int(sizes.sum())))
        cuts.append(np.cumsum(sizes))
        total += int(sizes.sum())
    buckets, order = eng.plan_buckets(table, cuts)
    assert len(order) == sum(len(c) for c in cuts) and len(buckets) >= 8
    for b in buckets:
        rows = fused_convert.bucket_rows(b.count)
        assert rows * b.cap_blocks * eng._unit_bytes() <= fused_convert.TILE_BYTES
        assert (len(b.offsets), len(b.sizes), b.tile_rows, b.tiles) == (rows, rows, rows, 1)


# (chunks, blocks a row): the widest class of each accepted cell's layers, as `pack:lane.plan` reports
# them (classes: [cap_blocks, live, rows]), and of mlimage-1m's layer
@pytest.mark.parametrize(
    "live,cap_blocks,rows,tile_rows",
    [
        (3810, 2048, 4096, 4096),  # node21-64k layer 0: 512 MiB, the widest batch of any accepted cell
        (75, 32768, 128, 128),  # node21-1m layer 0: 256 MiB
        (1, 65536, R, R),  # its one-chunk class at the row floor
        (115, 65536, 128, 128),  # mlimage-1m: 512 MiB, still one batch
        (576, 32768, 768, 256),  # mlimage-1m: 3 tiles of 512 MiB, where one batch would be 1,024 rows, 2 GiB
        (1025, 32768, 1280, 256),
    ],
)
def test_the_budget_leaves_the_accepted_cells_classes_whole(live, cap_blocks, rows, tile_rows):
    assert fused_convert.class_rows(live, cap_blocks * 64) == (rows, tile_rows)
    assert tile_rows * cap_blocks * 64 <= fused_convert.TILE_BYTES
    if tile_rows == rows:
        assert rows == fused_convert.bucket_rows(live)


def test_a_row_wider_than_the_budget_is_the_floors_batch(monkeypatch):
    monkeypatch.setattr(fused_convert, "TILE_BYTES", 1000)
    assert fused_convert.class_rows(1, 4096) == (R, R)
    assert fused_convert.class_rows(5, 4096) == (6, R)


# -- the served pack of a layer that a few huge files hold ---------------------

CHUNK = 0x10000
HUGE = [(6 * MIB + 12_345, "binary"), (3 * MIB + 777, "binary"), (MIB, "text")]
SERVED_TILE_BYTES = 2 * MIB


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A tar of three files that hold nearly all its bytes and forty small
    ones, packed on the device lane with the budget at 2 MiB and on the host
    lane -> (files, fused artifact, hybrid artifact, the fused pack's
    `pack:lane.plan`)."""
    d = tmp_path_factory.mktemp("row_tiles")
    members = [image.Member(f"huge/f{i}.so", size, kind) for i, (size, kind) in enumerate(HUGE)]
    members += [image.Member(f"small/f{i}.py", 300 + 97 * i, "text") for i in range(40)]
    datas = image.layer_bytes(5, 33, CHUNK // 4, 1, 0, members)
    image.write_tar(str(d / "layer.tar"), members, datas)
    args = ["--chunking", "cdc", "--fs-version", "v6", "--compressor", "lz4_block", "--digester", "sha256",
            "--chunk-size", hex(CHUNK)]
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fused_convert, "TILE_BYTES", SERVED_TILE_BYTES)
        trace.configure(enabled=True)
        try:
            for backend in ("fused", "hybrid"):
                path = str(d / f"layer.{backend}.nydus")
                line = run_cli("pack", "--in", str(d / "layer.tar"), "--out", path, "--backend", backend, *args)
                with open(path, "rb") as f:
                    out[backend] = (f.read(), line)
            spans = trace.snapshot_spans()
            plan = [dict(s.attrs) for s in spans if s.name == "pack:lane.plan"]
        finally:
            trace.reset()
    return {"files": [(m.name, data) for m, data in zip(members, datas)], **out, "plan": plan}


def test_the_served_pack_of_a_few_huge_files_equals_the_plain_reference(served):
    bs = bootstrap_from_layer_blob(served["fused"][0])
    by_path = {ino.path: bs.chunks[ino.chunk_index : ino.chunk_index + ino.chunk_count] for ino in bs.inodes}
    for name, data in served["files"]:
        want = reference.plain_chunks(data, CHUNK)
        assert [(c.uncompressed_size, c.digest) for c in by_path["/" + name]] == want, name
    assert len(by_path["/huge/f0.so"]) > 40
    assert served["fused"] == served["hybrid"]  # the artifact and the result line, byte for byte


def test_the_plan_span_says_what_was_tiled(served):
    (plan,) = served["plan"]  # the host lane plans nothing
    tiled = [(cap, live, rows) for cap, live, rows in plan["classes"]
             if fused_convert.bucket_rows(live) * cap * 64 > SERVED_TILE_BYTES]
    assert tiled and len(tiled) < len(plan["classes"])
    assert plan["blocks_tiled"] == sum(cap * rows for cap, _live, rows in tiled) > 0
    assert plan["blocks_tiled"] < plan["blocks_padded"]
    assert 0 < plan["batch_mib_max"] <= SERVED_TILE_BYTES / MIB
    # every tiled class is whole tiles of the budget's rows, and no more of them than its chunks need
    tiles = []
    for cap, live, rows in tiled:
        tile = fused_convert._pow2_floor(SERVED_TILE_BYTES // (cap * 64))
        assert rows == -(-live // tile) * tile
        tiles.append(rows // tile)
    assert plan["row_tiles"] == sum(t - 1 for t in tiles) >= len(tiled)
