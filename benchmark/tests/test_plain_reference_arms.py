"""The plain reference's arms: the cut rule (gear CDC over windows, or fixed
size), the digest (sha256, or BLAKE3 written from its specification) and the
stored bytes (lz4 block, or one zstd frame), chosen by a configuration's
``pack_args`` through ``verify.arms``; and the controls that show the arms
decide: a pack made with one argument other than the configuration states is
not correct. The program is imported here as a witness only;
``benchmark/reference.py`` imports nothing of it."""

import os
import shutil
import struct
import sys

import numpy as np
import pytest
import zstandard

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import program, reference, run, verify  # noqa: E402
from benchmark.traffic import convert_loop, image  # noqa: E402

# the BLAKE3 specification's test-vector inputs: byte i is i mod 251
VECTOR_LENGTHS = [0, 1, 63, 64, 65, 1023, 1024, 1025, 2048, 2049, 3072, 3073, 4096, 4097, 5120, 8192, 8193,
                  16384, 31744, 102400]
KNOWN = {0: "af1349b9f5f9a1a6a0404dea36dcc9499bcb25c9adc112b7cc9a93cae41f3262",  # tests/test_real_write.py
         1: "2d3adedff11b61f14c886e35afa036736dcd87a74d27b5c1510225d0f592e213"}
UPSTREAM_DEFAULTS = ["--backend", "hybrid", "--chunking", "fixed", "--fs-version", "v6", "--compressor", "zstd",
                     "--digester", "blake3", "--chunk-size", "0x10000"]


def vector(n: int) -> bytes:
    return bytes(i % 251 for i in range(n))


def parent_gear_hashes(data: np.ndarray) -> np.ndarray:
    """The whole-array formula, as the reference computed it over a whole file."""
    h = np.concatenate([np.zeros(31, np.uint32), reference.GEAR[data]])
    for w in (1, 2, 4, 8, 16):
        h[w:] += h[:-w] << np.uint32(w)
    return h[31:]


def parent_plain_chunks(data: np.ndarray, avg: int) -> list:
    """The reference's one arm before it had others: gear CDC over the whole file, sha256."""
    import hashlib

    n = len(data)
    bits = avg.bit_length() - 1
    lo, hi = avg // 4, 4 * avg
    mask_s, mask_l = np.uint32((1 << (bits + 2)) - 1), np.uint32((1 << (bits - 2)) - 1)
    h = parent_gear_hashes(data) if n > lo else None
    cuts, start = [], 0
    while n - start > lo:
        end = None
        a, b = start + lo - 1, min(start + avg - 1, n)
        hit = np.flatnonzero((h[a:b] & mask_s) == 0)
        if hit.size:
            end = a + int(hit[0]) + 1
        else:
            a, b = start + avg - 1, min(start + hi - 1, n)
            hit = np.flatnonzero((h[a:b] & mask_l) == 0)
            if hit.size:
                end = a + int(hit[0]) + 1
        if end is None:
            end = start + hi if n - start > hi else n
        cuts.append(end)
        start = end
    if n > start:
        cuts.append(n)
    return [(e - s, hashlib.sha256(memoryview(data[s:e])).digest()) for s, e in zip([0, *cuts[:-1]], cuts)]


def seeded_files(kind: str, seed: int = 42) -> list[np.ndarray]:
    """Files of one kind as the image generator draws them: sizes around 64 KiB and 1 MiB chunks' edges."""
    sizes = [0, 1, 31, 32, 5000, 16385, 70000, 262145, 1 << 20, 3_000_001, 9_437_185]
    members = [image.Member(f"f{i}", s, kind) for i, s in enumerate(sizes)]
    return image.layer_bytes(seed, seed, 0, 1, 0, members)


# -- BLAKE3 --------------------------------------------------------------------


@pytest.fixture(scope="module")
def program_blake3():
    """The program's BLAKE3s over the vectors: pure Python, the JAX kernel, the native engine where it is built."""
    from nydus_snapshotter_tpu.ops import blake3_jax, native_cdc
    from nydus_snapshotter_tpu.utils import blake3

    inputs = [vector(n) for n in VECTOR_LENGTHS]
    arms = {"python": [blake3.blake3(x) for x in inputs], "jax": blake3_jax.blake3_many(inputs)}
    if native_cdc.blake3_many_available():
        data = np.frombuffer(b"".join(inputs), np.uint8)
        starts = np.cumsum([0] + VECTOR_LENGTHS[:-1])
        out = native_cdc.blake3_many_native(data, np.stack([starts, VECTOR_LENGTHS], axis=1))
        arms["native"] = [out[i * 32:(i + 1) * 32] for i in range(len(inputs))]
    return arms


@pytest.mark.parametrize("n", VECTOR_LENGTHS)
def test_blake3_matches_the_program_and_the_written_digests(program_blake3, n):
    (got,) = reference.blake3_many([vector(n)])
    assert len(got) == 32
    i = VECTOR_LENGTHS.index(n)
    assert all(digests[i] == got for digests in program_blake3.values()), n
    if n in KNOWN:
        assert got.hex() == KNOWN[n]


def test_blake3_of_many_pieces_at_once_is_each_alone():
    rng = np.random.default_rng(3)
    pieces = [rng.integers(0, 256, int(n), dtype=np.uint8) for n in rng.integers(0, 40000, 200)]
    pieces += [np.zeros(0, np.uint8), np.zeros(1 << 20, np.uint8)]
    together = reference.blake3_many(pieces)
    assert together == [reference.blake3_many([p])[0] for p in pieces]
    batch = reference.B3_BATCH
    try:  # and in batches of a few pieces
        reference.B3_BATCH = 50000
        assert reference.blake3_many(pieces) == together
    finally:
        reference.B3_BATCH = batch


# -- the cut rules ---------------------------------------------------------------


@pytest.mark.parametrize("chunk", [0x1000, 0x10000, 0x100000])
def test_fixed_cuts_at_and_around_multiples_of_the_chunk_size(chunk):
    for n in (0, 1, chunk - 1, chunk, chunk + 1, 2 * chunk - 1, 2 * chunk, 2 * chunk + 1, 5 * chunk + 7):
        cuts = reference.plain_cuts(np.zeros(n, np.uint8), chunk, "fixed")
        assert cuts == [min(k * chunk, n) for k in range(1, -(-n // chunk) + 1)], n
        sizes = np.diff([0, *cuts])
        assert all(sizes[:-1] == chunk) and (n == 0 or 0 < sizes[-1] <= chunk)
    assert reference.plain_cuts(np.zeros(0, np.uint8), chunk, "fixed") == []
    with pytest.raises(ValueError):
        reference.plain_cuts(np.zeros(9, np.uint8), chunk, "rabin")


@pytest.mark.parametrize("kind", ["text", "binary", "random"])
def test_windowed_gear_hashes_are_the_whole_array_formula(kind):
    (data,) = [d for d in seeded_files(kind) if len(d) == 3_000_001]
    whole = parent_gear_hashes(data)
    block = reference.GEAR_BLOCK
    for start, stop in ((0, 1), (0, 100), (5, 40), (30, 31), (31, 1000), (32, 70000), (1 << 20, 3_000_001),
                        (2_999_990, 3_000_001), (0, 3_000_001), (block - 7, 2 * block + 9)):
        assert np.array_equal(reference.window_hashes(data, start, stop), whole[start:stop]), (start, stop)


@pytest.mark.parametrize("kind", ["text", "binary", "random"])
@pytest.mark.parametrize("window", [1, 100_000, 1 << 20, reference.WINDOW])
def test_windowed_cdc_cuts_are_the_whole_file_cuts(monkeypatch, kind, window):
    monkeypatch.setattr(reference, "WINDOW", window)
    for data in seeded_files(kind, seed=7):
        for avg in (0x1000, 0x10000, 0x100000):
            assert reference.plain_chunks(data, avg) == parent_plain_chunks(data, avg), (len(data), avg)


@pytest.mark.parametrize("config", ["node21-64k", "node21-1m", "smallfiles-64k"])
def test_the_default_arm_is_the_parent_formula_on_seeded_files(config):
    cfg = run.load(run.HERE, "configs", f"{config}.json")
    (members,) = image.image_shape(cfg["shape_seed"], cfg["file_law"], 6 << 20, [1])
    datas = image.layer_bytes(4200000003, cfg["data_seed"], cfg["chunk_size"] // 4, 1, 0, members)
    got = reference.plain_chunks_many(datas, cfg["chunk_size"])
    assert got == [parent_plain_chunks(d, cfg["chunk_size"]) for d in datas]
    assert got == [reference.plain_chunks(d, cfg["chunk_size"], "cdc", "sha256") for d in datas]


# -- stored bytes ----------------------------------------------------------------------


def test_a_zstd_frame_round_trips():
    from nydus_snapshotter_tpu.utils import zstd

    data = vector(300_000)
    frames = [zstandard.ZstdCompressor(level=3).compress(data)]
    if zstd.available():  # the system libzstd the program writes chunks with
        frames.append(zstd.compress_block(data))
    for frame in frames:
        assert reference.zstd_frame_decode(frame, len(data)) == data
        for bad, size in ((frame, len(data) - 1), (frame + frame, 2 * len(data)), (frame[:-3], len(data))):
            with pytest.raises(ValueError):
                reference.zstd_frame_decode(bad, size)


def test_a_frame_that_needs_a_dictionary_differs():
    from nydus_snapshotter_tpu.converter import codec

    samples = [b"%d common text of a corpus %d " % (i, i * 7) * 20 for i in range(2000)]
    trained = zstandard.train_dictionary(4096, samples)
    data = samples[5]
    frame = zstandard.ZstdCompressor(dict_data=trained).compress(data)
    assert zstandard.ZstdDecompressor(dict_data=trained).decompress(frame) == data
    with pytest.raises(ValueError):
        reference.zstd_frame_decode(frame, len(data))
    adaptive = struct.pack("<4sI", codec.TRAINED_FRAME_MAGIC, trained.dict_id()) + frame  # the adaptive codec's frame
    assert codec.is_trained_frame(adaptive)
    with pytest.raises(ValueError):
        reference.zstd_frame_decode(adaptive, len(data))


# -- the arms a configuration states -------------------------------------------------


def test_the_arms_are_the_configurations_with_the_clis_defaults():
    from nydus_snapshotter_tpu.cmd.convert import build_parser

    cli = build_parser().parse_args(["pack", "--in", "x", "--out", "y"])
    assert verify.arms({"pack_args": ["--backend", "fused"]}) == (
        {"avg": cli.chunk_size, "chunking": cli.chunking, "digester": cli.digester}, cli.compressor)
    assert verify.arms({"pack_args": UPSTREAM_DEFAULTS + ["--digester=sha256"]}) == (
        {"avg": 0x10000, "chunking": "fixed", "digester": "sha256"}, "zstd")
    with pytest.raises(SystemExit):
        verify.arms({"pack_args": ["--compressor", "gzip"]})
    for name in ("node21-64k", "node21-1m", "smallfiles-64k", "mlimage-1m", "tfimage-1m"):
        cfg = run.load(run.HERE, "configs", f"{name}.json")
        assert verify.arms(cfg)[0]["avg"] == cfg["chunk_size"]


@pytest.fixture(scope="module")
def upstream_loop(tmp_path_factory):
    """node21-64k's image at 8 MiB, its configuration stating upstream's defaults but for the chunk size."""
    program.prepare()
    config = run.load(run.HERE, "configs", "node21-64k.json") | {"image_mib": 8, "pack_args": UPSTREAM_DEFAULTS}
    cell = run.load(run.HERE, "traffic", "mixes", "fresh.json") | {"plain_sample_mib": 8}
    work = tmp_path_factory.mktemp("arms")
    loop = convert_loop.build(cell, config, 4200000011, str(work), lambda *_a, **_k: None)
    loop.generate()
    yield loop
    shutil.rmtree(work, ignore_errors=True)


@pytest.mark.parametrize("extra,fails", [
    ([], set()),
    (["--digester", "sha256"], {"plain_files_differ"}),
    (["--chunking", "cdc"], {"plain_files_differ"}),
    (["--compressor", "lz4_block"], {"stored_chunks_differ", "stored_compressed_compared"}),
    (["--compressor", "none"], {"stored_compressed_compared"}),
], ids=["as-stated", "digester-sha256", "chunking-cdc", "compressor-lz4_block", "compressor-none"])
def test_the_arms_decide_a_hybrid_pack(upstream_loop, extra, fails):
    """A pack with the configuration's own arguments is correct; with one of them
    changed, the configuration kept, the check of that argument fails."""
    loop = upstream_loop
    out = os.path.join(loop.work, "-".join(extra) or "as-stated")
    os.makedirs(out)
    for _verb, _layer, _n, argv in loop.verbs(out, backend="hybrid", extra=extra):
        program.cli(argv)
    own = {verify.blob_sha256(os.path.join(out, f"layer{li}.nydus")) for li in range(len(loop.tars))}
    checks = {c["name"]: c for c in verify.plain_checks(loop, out, own, lambda *_a, **_k: None)}
    assert {name for name, c in checks.items() if not c["ok"]} == fails, checks
    assert checks["plain_chunks_compared"]["value"] > 100 and checks["stored_chunks_compared"]["value"] == 32
