"""The `smallfiles-64k` configuration at a small size on the CPU: a layer of
thousands of files drawn with the configuration's own law (a `pip install`
layer as measured: 87% no longer than the chunker's min_size, one chunk each),
plus the edge sizes of the digest layout and of the cut rule, through
`cmd.convert pack --backend fused`, without and with a chunk dictionary.

The plain reference (`benchmark/reference.py`: gear CDC, hashlib; imports
nothing of the program) decides the chunk records; `--backend hybrid` is the
byte-for-byte witness.

CPU backend: the fused lane runs its XLA formulation (`--jax-platform cpu`).
"""

import contextlib
import hashlib
import io
import json
import os

import numpy as np
import pytest

from benchmark import reference
from benchmark.traffic import image
from nydus_snapshotter_tpu import trace
from nydus_snapshotter_tpu.cmd import convert as cli
from nydus_snapshotter_tpu.converter.convert import blob_data_from_layer_blob, bootstrap_from_layer_blob
from nydus_snapshotter_tpu.ops import cdc, fused_convert

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs", "smallfiles-64k.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "benchmark", "configs", "node21-64k.json")) as f:
    SIBLING = json.load(f)
CHUNK = CONFIG["chunk_size"]
MIN_SIZE = cdc.CDCParams(CHUNK).min_size
LAYER_MIB = 32  # ~2,800 files by the law: its widest classes hold hundreds of rows, as the cell's hold thousands
# the digest layout's block edges (a sha256 block holds 55 bytes beside its padding), the cut rule's
# (min_size: one chunk and no candidate judged up to it) and one byte past the largest chunk
EDGE_SIZES = [0, 1, 55, 56, 63, 64, 65, MIN_SIZE - 1, MIN_SIZE, MIN_SIZE + 1, 4 * CHUNK + 1]
TWIN_BYTES = 777
DICTS = [False, True]


def run_cli(*argv) -> dict:
    """cmd.convert.main -> its result line."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert cli.main(["--jax-platform", "cpu", *argv]) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def pack_args(backend: str) -> list[str]:
    args = list(CONFIG["pack_args"])
    args[args.index("--backend") + 1] = backend
    return args


@pytest.fixture(scope="module")
def layer(tmp_path_factory):
    """-> the layer's files [(name, u8 array)], its tar, and a dictionary (the
    merged bootstrap of an image that holds every third of them)."""
    d = tmp_path_factory.mktemp("smallfiles")
    (members,) = image.image_shape(CONFIG["shape_seed"], CONFIG["file_law"], LAYER_MIB << 20, [1])
    files = list(zip(members, image.layer_bytes(7, CONFIG["data_seed"], CHUNK // 4, 1, 0, members)))
    rng = np.random.default_rng(29)
    drawn = lambda name, size: (image.Member(name, size, "random"), rng.integers(0, 256, size, dtype=np.uint8))
    files += [drawn(f"edge/s{size}.bin", size) for size in EDGE_SIZES]
    twin_a, twin = drawn("edge/twin_a.bin", TWIN_BYTES)
    shared = files[::3] + [(twin_a, twin)]  # the dictionary image holds the twins' bytes once
    files += [(twin_a, twin), (image.Member("edge/twin_b.bin", TWIN_BYTES, "random"), twin)]
    image.write_tar(str(d / "layer.tar"), *zip(*files))
    image.write_tar(str(d / "dict.tar"), *zip(*shared, drawn("other/only_here.bin", 300_000)))
    files = [(m.name, data) for m, data in files]
    run_cli("pack", "--in", str(d / "dict.tar"), "--out", str(d / "dict.nydus"), *pack_args("hybrid"))
    run_cli("merge", "--out", str(d / "dict.boot"), str(d / "dict.nydus"))
    # what the dictionary holds, by the plain reference: a chunk is deduplicated by its digest, whichever
    # file it came from (two text files can share a chunk: both are windows of one base)
    held = {digest for _m, data in shared for _size, digest in reference.plain_chunks(data, CHUNK)}
    return {"dir": d, "files": files, "held": held}


@pytest.fixture(scope="module")
def converts(layer):
    """Every pack of the module, made once: {(backend, with_dict): (artifact
    bytes, result line, the fused pack's leaf attributes)}."""
    out, d = {}, layer["dir"]
    trace.configure(enabled=True)
    try:
        for with_dict in DICTS:
            extra = ["--chunk-dict", str(d / "dict.boot")] if with_dict else []
            for backend in ("fused", "hybrid"):
                path = str(d / f"layer.{backend}.{int(with_dict)}.nydus")
                line = run_cli("pack", "--in", str(d / "layer.tar"), "--out", path, *pack_args(backend), *extra)
                spans = trace.snapshot_spans()
                root = [s for s in spans if s.name == "convert.pack"][-1]
                attrs = {s.name: dict(s.attrs) for s in spans if s.parent_id == root.span_id}
                with open(path, "rb") as f:
                    out[backend, with_dict] = (f.read(), line, attrs)
    finally:
        trace.reset()
    return out


def chunk_records(artifact: bytes) -> tuple[dict, object]:
    """-> ({path: its chunk records}, the layer's bootstrap)."""
    bs = bootstrap_from_layer_blob(artifact)
    return {ino.path: bs.chunks[ino.chunk_index:ino.chunk_index + ino.chunk_count] for ino in bs.inodes}, bs


def test_the_layer_is_the_configurations_shape(layer):
    sizes = np.array([len(data) for _name, data in layer["files"]])
    assert 2500 <= len(sizes) <= 3500
    assert 0.83 <= np.mean(sizes <= MIN_SIZE) <= 0.91  # seven files in eight are one chunk each
    assert np.sum(sizes[sizes > MIN_SIZE]) / np.sum(sizes) > 0.7  # and most bytes are in the rest
    # the same pack as the sibling whose every line it shares
    assert CONFIG["pack_args"] == SIBLING["pack_args"] and CONFIG["guarantees"] == SIBLING["guarantees"]


@pytest.mark.parametrize("with_dict", DICTS)
def test_chunk_records_equal_the_plain_reference(layer, converts, with_dict):
    by_path, bs = chunk_records(converts["fused", with_dict][0])
    own = bs.blobs.index(next(b for b in bs.blobs if b.blob_id == converts["fused", with_dict][1]["blob_id"]))
    differ = misplaced = hits = 0
    for name, data in layer["files"]:
        want = reference.plain_chunks(data, CHUNK)
        recs = by_path["/" + name]
        differ += [(c.uncompressed_size, c.digest) for c in recs] != want
        # a chunk the dictionary image holds is referenced there, every other chunk in the layer's own blob
        held = [with_dict and c.digest in layer["held"] for c in recs]
        misplaced += sum((c.blob_index == own) == h for c, h in zip(recs, held))
        hits += sum(held)
    assert differ == 0 and misplaced == 0
    assert (hits > 0) == with_dict
    assert len(by_path["/edge/s0.bin"]) == 0 and len(by_path[f"/edge/s{MIN_SIZE}.bin"]) == 1
    assert [c.uncompressed_size for c in by_path[f"/edge/s{4 * CHUNK + 1}.bin"]][-1] >= 1


@pytest.mark.parametrize("with_dict", DICTS)
def test_blob_id_is_the_sha256_of_the_blob_section(converts, with_dict):
    artifact, line, _attrs = converts["fused", with_dict]
    assert line["blob_id"] == hashlib.sha256(blob_data_from_layer_blob(artifact)).hexdigest()


@pytest.mark.parametrize("with_dict", DICTS)
def test_identical_files_store_one_chunk(converts, with_dict):
    by_path, _bs = chunk_records(converts["fused", with_dict][0])
    (a,), (b,) = by_path["/edge/twin_a.bin"], by_path["/edge/twin_b.bin"]
    assert (a.blob_index, a.compressed_offset, a.compressed_size) == (b.blob_index, b.compressed_offset, b.compressed_size)
    assert a.uncompressed_size == TWIN_BYTES


@pytest.mark.parametrize("with_dict", DICTS)
def test_the_artifact_equals_the_host_lanes(converts, with_dict):
    assert converts["fused", with_dict][1] == converts["hybrid", with_dict][1]
    assert converts["fused", with_dict][0] == converts["hybrid", with_dict][0]


@pytest.mark.parametrize("with_dict", DICTS)
def test_resolve_span_counts_the_single_chunk_files(layer, converts, with_dict):
    attrs = converts["fused", with_dict][2]
    sizes = [len(data) for _name, data in layer["files"]]
    resolve, plan = attrs["pack:lane.resolve"], attrs["pack:lane.plan"]
    assert resolve["files"] == sum(1 for s in sizes if s)  # an empty file never reaches the lane
    assert resolve["single_chunk_files"] == sum(1 for s in sizes if 0 < s <= MIN_SIZE)
    assert resolve["single_chunk_files"] / resolve["files"] > 0.83
    # the other regime of the plan: its widest class holds hundreds of rows where node21's long classes hold 2-128
    _cap, rows, padded = max(plan["classes"], key=lambda c: c[2])
    assert padded == fused_convert.bucket_rows(rows) >= 256
    assert attrs["pack:scan"]["files_planned"] == resolve["files"]
