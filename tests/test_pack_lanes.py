"""The lane seam of the converter (converter/stream.py): `choose_lane` is
the one place a pack's lane is decided, `read_layer` asks the same
question before it reads, a lane hides its algorithm behind one signature
(a test can put another in its place), and `emit_bootstrap` stands alone.

CPU backend: the device lane runs its XLA formulation.
"""

import dataclasses
import hashlib
import io
import tarfile
from types import SimpleNamespace

import numpy as np
import pytest

from nydus_snapshotter_tpu import trace
from nydus_snapshotter_tpu.converter import stream
from nydus_snapshotter_tpu.converter.convert import Pack, _make_compressor
from nydus_snapshotter_tpu.converter.types import PackOption
from nydus_snapshotter_tpu.models import fstree, toc
from nydus_snapshotter_tpu.models.bootstrap import Bootstrap, ChunkDict
from nydus_snapshotter_tpu.ops import native_cdc
from nydus_snapshotter_tpu.ops.chunker import ChunkDigestEngine

CHUNK = 0x10000


def arms(*missing: str):
    """A stand-in for ops/native_cdc: every arm loaded but the `missing`."""
    names = ("chunk_digest_multi", "pack_files", "pack_section")
    return SimpleNamespace(**{f"{n}_available": (lambda n=n: n not in missing) for n in names})


def opt(backend="hybrid", **kw) -> PackOption:
    return PackOption(backend=backend, chunk_size=CHUNK, **kw)


# (what the pack observes) -> the lane's name; `host_fused` is what
# IncrementalChunker.fused says of that opt where libchunk_engine loaded
LANE_TABLE = [
    ("whole layer natively", opt(), dict(threads=1, host_fused=True), "_lane_native_whole"),
    ("a dictionary keeps the dedup in Python", opt(), dict(threads=1, host_fused=True, has_dict=True),
     "_lane_native_multi"),
    ("the walk seeded chunk state (a sparse member)", opt(), dict(threads=1, host_fused=True, seeded=True),
     "_lane_native_multi"),
    ("an encrypted section is not the deferred writer's", opt(encrypt=True), dict(threads=1, host_fused=True),
     "_lane_native_multi"),
    ("batch packing is not the deferred writer's", opt(batch_size=0x1000), dict(threads=1, host_fused=True),
     "_lane_native_multi"),
    ("an active adaptive codec owns the frames", opt(compressor="zstd"),
     dict(threads=1, host_fused=True, codec_active=True), "_lane_native_multi"),
    ("no ntpu_pack_files", opt(), dict(threads=1, host_fused=True, native=arms("pack_files")), "_lane_native_multi"),
    ("no ntpu_pack_section", opt(), dict(threads=1, host_fused=True, native=arms("pack_section")),
     "_lane_native_multi"),
    ("the whole-layer arm declined", opt(), dict(threads=1, host_fused=True, declined=[stream._lane_native_whole]),
     "_lane_native_multi"),
    ("no native arm at all", opt(), dict(threads=1, host_fused=False, native=arms("chunk_digest_multi", "pack_files")),
     "_lane_per_file"),
    ("fixed chunking", opt(chunking="fixed"), dict(threads=1, host_fused=False), "_lane_per_file"),
    ("more than one thread", opt(), dict(threads=4, host_fused=True), "_lane_per_file_workers"),
    ("numpy chunks on workers too", opt("numpy"), dict(threads=4, host_fused=False), "_lane_per_file_workers"),
    ("jax keeps its own dispatch discipline", opt("jax"), dict(threads=4, host_fused=False), "_lane_per_file"),
    ("device digests keep it too", opt(digest_backend="jax"), dict(threads=4, host_fused=False), "_lane_per_file"),
    ("--backend fused on a one-thread host: the device lane, not a native one", opt("fused"),
     dict(threads=1, host_fused=False), "_lane_device"),
    ("--backend fused with threads and a dictionary", opt("fused"), dict(threads=8, host_fused=False, has_dict=True),
     "_lane_device"),
    # the lane is not picked by the layer's size: one that no lane buffer holds is still the device
    # lane's, which packs it as batches of whole files (tests/test_lane_batches.py); choose_lane sees
    # its size nowhere, and _begin_device_lane having begun nothing for it changes nothing here
    ("--backend fused and a layer past one lane buffer: the device lane, in batches", opt("fused"),
     dict(threads=13, host_fused=False, seeded=False), "_lane_device"),
    ("the device lane cuts CDC only", opt("fused", chunking="fixed"), dict(threads=1, host_fused=False),
     "_lane_per_file"),
    ("the device lane declined (FusedOverflow)", opt("fused"),
     dict(threads=1, host_fused=False, declined=[stream._lane_device]), "_lane_per_file"),
    ("a file-like source takes no batch lane", opt(), dict(threads=1, host_fused=True, in_memory=False), None),
    ("nor does one with --backend fused", opt("fused"), dict(threads=1, host_fused=False, in_memory=False), None),
]


@pytest.mark.parametrize("why,option,observed,want", LANE_TABLE, ids=[row[0] for row in LANE_TABLE])
def test_choose_lane_decision_table(why, option, observed, want):
    seen = dict(in_memory=True, has_dict=False, codec_active=False, seeded=False, native=arms()) | observed
    lane = stream.choose_lane(option, **seen)
    assert (lane and lane.__name__) == want, why
    if native_cdc.chunk_digest_available() and observed.get("native") is None:
        # the table's host_fused is the real chunker's answer for that opt
        assert stream.IncrementalChunker(option).fused == observed["host_fused"]


def make_tar(sizes=(300_000, 2_000, 450_000, 900), seed=7) -> bytes:
    rng = np.random.default_rng(seed)
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tf:
        for i, size in enumerate(sizes):
            info = tarfile.TarInfo(f"d{i % 2}/f{i}")
            info.size = size
            tf.addfile(info, io.BytesIO(rng.integers(0, 256, size, dtype=np.uint8).tobytes()))
    return buf.getvalue()


def lane_leaves() -> dict:
    spans = trace.snapshot_spans()
    root = [s for s in spans if s.name == "convert.pack"][-1]
    return {s.name: s.attrs for s in spans if s.parent_id == root.span_id}


READER_OPTS = [
    opt("fused"), opt("fused", digester="blake3"), opt("fused", chunk_dict_path="x"), opt("fused", chunking="fixed"),
    opt("fused", oci_ref=True), opt("hybrid"), opt("numpy"), opt("jax"), opt("hybrid", chunking="fixed"),
]


@pytest.mark.parametrize("option", READER_OPTS, ids=lambda o: f"{o.backend}-{o.chunking}-{o.digester}"
                         f"{'-dict' if o.chunk_dict_path else ''}{'-oci' if o.oci_ref else ''}")
def test_read_layer_and_choose_lane_agree(tmp_path, option):
    """The reader hands back a lane buffer exactly for the packs that take
    the device lane, which then has nothing to copy; any other pack gets
    plain bytes."""
    tar = make_tar()
    (tmp_path / "l.tar").write_bytes(tar)
    with open(tmp_path / "l.tar", "rb") as f:
        src = stream.read_layer(f, option)
    assert bytes(src) == tar
    if not isinstance(src, np.ndarray):
        assert type(src) is bytes and not (stream._device_lane_wanted(option) and not option.oci_ref)
        return
    lane = stream.choose_lane(
        option, in_memory=True, threads=stream._pack_threads(), host_fused=stream.IncrementalChunker(option).fused,
        has_dict=bool(option.chunk_dict_path), codec_active=False, seeded=False,
    )
    assert lane is stream._lane_device
    trace.configure(enabled=True)
    try:
        # (the lane is the same with a dictionary: not this test's to build)
        Pack(io.BytesIO(), src, dataclasses.replace(option, chunk_dict_path=""))
        layout = lane_leaves()["pack:lane.layout"]
    finally:
        trace.reset()
    assert layout["bytes"] == len(tar) and layout["copied_bytes"] == 0


def test_read_layer_of_an_empty_file_is_plain_bytes(tmp_path):
    (tmp_path / "empty").write_bytes(b"")
    with open(tmp_path / "empty", "rb") as f:
        assert stream.read_layer(f, opt("fused")) == b""


def test_a_lane_can_be_substituted(monkeypatch):
    """The seam hides the algorithm: a lane that cuts and digests with the
    plain numpy engine gives the hybrid pack's blob and bootstrap byte for
    byte."""
    tar = make_tar(sizes=(500_000, 1_500, 70_000, 260_000, 1_500))
    want = io.BytesIO()
    want_res = Pack(want, tar, opt())
    engine = ChunkDigestEngine(chunk_size=CHUNK, backend="numpy", digest_backend="numpy")
    ran = []

    def numpy_lane(pack, plan, arr, stages):
        stages.next("pack:chunk_digest")
        ran.append(len(plan))
        for chunks in engine.process_many([arr[off : off + size] for _meta, off, size in plan]):
            yield [c.offset + c.size for c in chunks], [c.digest for c in chunks]

    monkeypatch.setattr(stream, "choose_lane", lambda option, **seen: numpy_lane)
    got = io.BytesIO()
    got_res = Pack(got, tar, opt())
    assert ran == [5]
    assert got.getvalue() == want.getvalue() and got_res.bootstrap == want_res.bootstrap


def test_a_lane_must_answer_for_every_planned_file(monkeypatch):
    def short_lane(pack, plan, arr, stages):
        stages.next("pack:chunk_digest")
        yield [plan[0][2]], [hashlib.sha256(bytes(arr[plan[0][1] : plan[0][1] + plan[0][2]])).digest()]

    monkeypatch.setattr(stream, "choose_lane", lambda option, **seen: short_lane)
    with pytest.raises(ValueError, match="shorter"):
        Pack(io.BytesIO(), make_tar(sizes=(900, 800)), opt())


def test_a_declined_lane_is_followed_by_the_next(monkeypatch):
    """A lane that declines before its first result leaves the plan to the
    next lane choose_lane names, and is told to it as declined."""
    tar = make_tar()
    want = io.BytesIO()
    Pack(want, tar, opt("numpy"))
    asked = []
    real = stream.choose_lane

    def declining(pack, plan, arr, stages):
        raise stream._LaneDeclined("not today")
        yield

    def choose(option, **seen):
        asked.append(list(seen["declined"]))
        return declining if not seen["declined"] else real(option, **seen)

    monkeypatch.setattr(stream, "choose_lane", choose)
    got = io.BytesIO()
    Pack(got, tar, opt("numpy"))
    assert asked == [[], [declining]] and got.getvalue() == want.getvalue()


def member(name: str, data: bytes = b"", **kw) -> tuple[tarfile.TarInfo, bytes]:
    info = tarfile.TarInfo(name)
    info.size = len(data)
    info.mtime = 1_700_000_000
    for k, v in kw.items():
        setattr(info, k, v)
    return info, data


def test_emit_bootstrap_alone():
    """From hand-built metas (a whiteout, an opaque dir, a repeated path, a
    dictionary hit) and an assembler fed by hand: the bootstrap and TOC
    pack_stream writes for the same layer."""
    rng = np.random.default_rng(3)
    shared, first, second, other = (rng.integers(0, 256, n, dtype=np.uint8).tobytes() for n in (3000, 2000, 2500, 900))
    dict_blob = io.BytesIO()
    Pack(dict_blob, tar_of([member("lib/shared.so", shared)]), opt())
    from nydus_snapshotter_tpu.converter.convert import bootstrap_from_layer_blob

    chunk_dict = ChunkDict(bootstrap_from_layer_blob(dict_blob.getvalue()))
    members = [
        member("app", type=tarfile.DIRTYPE, mode=0o755),
        member("app/shared.so", shared, mode=0o644, uid=7),
        member("app/conf", first, mode=0o600),
        member("app/.wh.gone"),
        member("app/conf", second, mode=0o640),  # the path again: the last one wins
        member("cache/.wh..wh..opq"),
        member("cache/new", other, mode=0o644),
    ]
    option = opt(fs_version="v5")
    blob = io.BytesIO()
    res = Pack(blob, tar_of(members), option, chunk_dict=chunk_dict)
    assert len(res.referenced_blob_ids) == 2  # its own and the dictionary's

    metas, opaque_dirs = {}, []
    section = stream._SectionWriter(
        stream._CountingWriter(io.BytesIO()), option, _make_compressor(option.compressor, option.lz4_acceleration)
    )
    asm = stream._Assembler(section, chunk_dict)
    for info, data in members:
        path = fstree.norm_path(info.name)
        special = fstree.classify_special(path)
        if special is not None:
            kind, target = special
            if kind == "opaque":
                opaque_dirs.append(target)
            else:
                metas[target] = stream._Meta(entry=fstree.whiteout_entry(target))
            continue
        meta = metas[path] = stream._Meta(entry=fstree.entry_from_tarinfo(None, info, path, with_data=False))
        if data:
            meta.size = len(data)
            asm.process([(meta, data)], [hashlib.sha256(data).digest()])  # each a single chunk (< min_size)
    section.finish()
    assert len(asm.dict_hits) == 1 and len(asm.uncomp_offsets) == 3  # the overwritten conf's bytes stay in the blob
    boot_off = section.coff + 512
    bootstrap, boot_bytes, entries = stream.emit_bootstrap(metas, opaque_dirs, asm, option, boot_off)
    assert boot_bytes == res.bootstrap
    by_path = {i.path: i for i in Bootstrap.from_bytes(boot_bytes).inodes}
    assert by_path["/app/conf"].size == len(second) and "/cache" in by_path and "/" in by_path
    toc_bytes = toc.pack_toc(entries)
    assert blob.getvalue()[boot_off : boot_off + len(boot_bytes)] == boot_bytes
    assert blob.getvalue()[-512 - len(toc_bytes) : -512] == toc_bytes
    assert [e.name for e in entries] == [toc.ENTRY_BLOB_DATA, toc.ENTRY_BOOTSTRAP]
    assert [b.blob_id for b in bootstrap.blobs] == res.referenced_blob_ids


def tar_of(members) -> bytes:
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tf:
        for info, data in members:
            tf.addfile(info, io.BytesIO(data))
    return buf.getvalue()
