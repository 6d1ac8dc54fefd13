"""Streaming Pack: bounded memory, incremental-chunker equivalence.

Reference bar: conversion memory independent of layer size (the 1 MiB FIFO
discipline of pkg/converter/convert_unix.go:56-61,443-539). The 4 GiB /
<1 GiB RSS criterion runs out-of-band; here a CI-sized layer asserts the
same property via VmHWM deltas, and the incremental chunker is
differential-tested against whole-stream chunking.
"""

import io
import os
import subprocess
import sys
import tarfile

import numpy as np
import pytest

from nydus_snapshotter_tpu.converter.convert import (
    Unpack,
    blob_data_from_layer_blob,
    pack_layer,
)
from nydus_snapshotter_tpu.converter.stream import IncrementalChunker, pack_stream
from nydus_snapshotter_tpu.converter.types import PackOption
from nydus_snapshotter_tpu.ops import cdc

from tests.test_converter import build_tar, tar_tree, _rand

RNG = np.random.default_rng(23)


class TestIncrementalChunker:
    @pytest.mark.parametrize("seg", [1 << 12, 1 << 16, 1 << 20])
    def test_cdc_matches_whole_stream(self, seg):
        data = RNG.integers(0, 256, 3_000_000, dtype=np.uint8).tobytes()
        opt = PackOption(chunk_size=0x10000, backend="numpy")
        ch = IncrementalChunker(opt)
        pairs = []
        for off in range(0, len(data), seg):
            pairs.extend(ch.feed(data[off : off + seg]))
        pairs.extend(ch.finish())
        chunks = [c for c, _ in pairs]
        assert b"".join(chunks) == data
        sizes = np.cumsum([len(c) for c in chunks])
        want = cdc.chunk_data_np(np.frombuffer(data, np.uint8), cdc.CDCParams(0x10000))
        assert np.array_equal(sizes, want)
        assert all(d is None for _, d in pairs)  # numpy backend never fuses

    def test_fused_hybrid_matches_numpy_and_hashlib(self):
        import hashlib

        from nydus_snapshotter_tpu.ops import native_cdc

        if not native_cdc.chunk_digest_available():
            pytest.skip("fused native arm unavailable")
        data = RNG.integers(0, 256, 2_500_000, dtype=np.uint8).tobytes()
        ch = IncrementalChunker(PackOption(chunk_size=0x10000, backend="hybrid"))
        assert ch.fused
        pairs = []
        for off in range(0, len(data), 1 << 18):
            pairs.extend(ch.feed(data[off : off + (1 << 18)]))
        pairs.extend(ch.finish())
        chunks = [c for c, _ in pairs]
        assert b"".join(chunks) == data
        sizes = np.cumsum([len(c) for c in chunks])
        want = cdc.chunk_data_np(np.frombuffer(data, np.uint8), cdc.CDCParams(0x10000))
        assert np.array_equal(sizes, want)
        assert all(d == hashlib.sha256(c).digest() for c, d in pairs)

    def test_fixed_matches_whole_stream(self):
        data = RNG.integers(0, 256, 1_000_001, dtype=np.uint8).tobytes()
        opt = PackOption(chunk_size=0x10000, backend="numpy", chunking="fixed")
        ch = IncrementalChunker(opt)
        chunks = []
        for off in range(0, len(data), 70_000):
            chunks.extend(c for c, _ in ch.feed(data[off : off + 70_000]))
        chunks.extend(c for c, _ in ch.finish())
        assert b"".join(chunks) == data
        assert all(len(c) == 0x10000 for c in chunks[:-1])

    def test_tiny_and_empty_streams(self):
        opt = PackOption(chunk_size=0x10000, backend="numpy")
        ch = IncrementalChunker(opt)
        assert ch.feed(b"") == []
        assert ch.finish() == []
        ch = IncrementalChunker(opt)
        assert ch.feed(b"abc") == []
        assert [c for c, _ in ch.finish()] == [b"abc"]


class TestStreamPack:
    def test_stream_and_bytes_inputs_identical(self):
        files = [("a/x", _rand(200_000)), ("a/y", _rand(50_000))]
        src = build_tar(files, dirs=["a"])
        opt = PackOption(backend="numpy")
        blob1, res1 = pack_layer(src, opt)
        out = io.BytesIO()
        res2 = pack_stream(out, io.BytesIO(src), opt)
        assert out.getvalue() == blob1
        assert res2.blob_id == res1.blob_id

    def test_unseekable_dest(self):
        # dest without tell(): only write() is required.
        class WriteOnly:
            def __init__(self):
                self.chunks = []

            def write(self, b):
                self.chunks.append(bytes(b))

        files = [("f/one", _rand(100_000))]
        src = build_tar(files, dirs=["f"])
        dst = WriteOnly()
        res = pack_stream(dst, io.BytesIO(src), PackOption(backend="numpy"))
        blob = b"".join(dst.chunks)
        out = Unpack(res.bootstrap, {res.blob_id: blob_data_from_layer_blob(blob)})
        assert tar_tree(out)["/f/one"][1] == files[0][1]

    def test_duplicate_path_last_wins(self):
        out = io.BytesIO()
        with tarfile.open(fileobj=out, mode="w:") as tf:
            for payload in (b"first" * 100, b"second" * 100):
                ti = tarfile.TarInfo("dup/file")
                ti.size = len(payload)
                tf.addfile(ti, io.BytesIO(payload))
        blob, res = pack_layer(out.getvalue(), PackOption(backend="numpy"))
        unpacked = Unpack(res.bootstrap, {res.blob_id: blob_data_from_layer_blob(blob)})
        assert tar_tree(unpacked)["/dup/file"][1] == b"second" * 100

    def test_bounded_memory_subprocess(self, tmp_path):
        # 256 MiB layer must pack within a ~160 MiB RSS envelope above the
        # post-import baseline (whole-layer materialization would add 256+).
        layer = tmp_path / "layer.tar"
        script = f"""
import os, sys, tarfile
import numpy as np
sys.path.insert(0, {os.getcwd()!r})

rng = np.random.default_rng(1)
base = rng.integers(0, 256, 4 << 20, dtype=np.uint8)
with tarfile.open({str(layer)!r}, "w") as tf:
    class Gen:
        def __init__(self, n): self.n = n; self.off = 0
        def read(self, k=-1):
            if self.off >= self.n: return b""
            k = min(k if k > 0 else self.n, self.n - self.off, 4 << 20)
            out = np.roll(base, -(self.off % 97)) [:k].tobytes()
            self.off += k
            return out
    ti = tarfile.TarInfo("big/blob"); ti.size = 256 << 20
    tf.addfile(ti, Gen(ti.size))

def vmhwm():
    for line in open("/proc/self/status"):
        if line.startswith("VmHWM"):
            return int(line.split()[1]) // 1024

from nydus_snapshotter_tpu.converter.types import PackOption
from nydus_snapshotter_tpu.converter.stream import pack_stream
base_rss = vmhwm()
with open({str(layer)!r}, "rb") as src, open(os.devnull, "wb") as dst:
    pack_stream(dst, src, PackOption(backend="numpy", compressor="none", chunk_size=0x100000))
delta = vmhwm() - base_rss
print("RSS_DELTA_MIB", delta)
assert delta < 160, f"streaming pack used {{delta}} MiB over baseline"
"""
        proc = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True, timeout=300
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "RSS_DELTA_MIB" in proc.stdout


class TestDeferredNativeSection:
    """The one-native-pass blob assembly (_DeferredSectionWriter) must be
    byte-equivalent to the per-chunk Python section writer in every
    configuration that activates it."""

    def _layer(self, seed=17, n=30):
        rng = np.random.default_rng(seed)
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w", format=tarfile.GNU_FORMAT) as tf:
            for i in range(n):
                size = int(rng.integers(1, 300_000))
                ti = tarfile.TarInfo(f"d{i % 5}/f{i}")
                ti.size = size
                data = rng.integers(0, 256, size, dtype=np.uint8)
                if i % 3 == 0:
                    data[: size // 2] = 0x42  # compressible half
                tf.addfile(ti, io.BytesIO(data.tobytes()))
        return buf.getvalue()

    def _python_section_blob(self, raw, opt):
        """Pack via the streaming (file-like) path, which always uses the
        per-chunk Python _SectionWriter."""
        out = io.BytesIO()
        pack_stream(out, io.BytesIO(raw), opt)
        return out.getvalue()

    @pytest.mark.parametrize("compressor", ["lz4_block", "none"])
    def test_identical_to_python_writer(self, compressor):
        raw = self._layer()
        opt = PackOption(chunk_size=0x10000, compressor=compressor)
        blob_fast, _ = pack_layer(raw, opt)
        assert blob_fast == self._python_section_blob(raw, opt)

    def test_threaded_native_identical(self, monkeypatch):
        raw = self._layer(seed=23)
        opt = PackOption(chunk_size=0x10000)
        monkeypatch.setenv("NTPU_PACK_THREADS", "1")
        one, _ = pack_layer(raw, opt)
        monkeypatch.setenv("NTPU_PACK_THREADS", "4")
        monkeypatch.setenv("NTPU_PACK_THREADS_FORCE", "1")
        four, _ = pack_layer(raw, opt)
        assert one == four

    def test_lz4_acceleration_roundtrip(self):
        raw = self._layer(seed=29)
        opt = PackOption(chunk_size=0x10000, lz4_acceleration=6)
        blob, res = pack_layer(raw, opt)
        # fast (native) and streaming (python) paths agree at accel != 1
        assert blob == self._python_section_blob(raw, opt)
        # and the image round-trips
        from nydus_snapshotter_tpu.converter.convert import bootstrap_from_layer_blob

        bs = bootstrap_from_layer_blob(blob)
        assert bs.chunks, "expected chunks"
        from nydus_snapshotter_tpu.converter.types import ConvertError

        try:
            PackOption(lz4_acceleration=0).validate()
            raise AssertionError("accel 0 must be rejected")
        except ConvertError:
            pass


class TestDeferredDifferentialFuzz:
    """Randomized differential: for many random tar shapes (file sizes
    across chunk boundaries, duplicates, symlinks/dirs/empties, pax and
    GNU formats, both compressors, and a chunk-dict trial), the in-memory
    fast path (native deferred section) and the file-like streaming path
    (Python section writer) must produce byte-identical layer blobs, and
    the blob must round-trip through Unpack."""

    def _random_layer(self, rng, fmt):
        buf = io.BytesIO()
        n = int(rng.integers(1, 25))
        shared = rng.integers(0, 256, 70_000, dtype=np.uint8).tobytes()
        with tarfile.open(fileobj=buf, mode="w", format=fmt) as tf:
            for i in range(n):
                kind = rng.random()
                name = f"d{int(rng.integers(0, 4))}/n{i}"
                if kind < 0.12:
                    ti = tarfile.TarInfo(name)
                    ti.type = tarfile.DIRTYPE
                    tf.addfile(ti)
                elif kind < 0.2:
                    ti = tarfile.TarInfo(name)
                    ti.type = tarfile.SYMTYPE
                    ti.linkname = "n0"
                    tf.addfile(ti)
                else:
                    size = int(rng.choice([0, 1, 100, 4095, 4096, 4097,
                                           65535, 65536, 65537,
                                           int(rng.integers(1, 400_000))]))
                    if rng.random() < 0.3:
                        data = (shared * (size // len(shared) + 1))[:size]
                    else:
                        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
                    ti = tarfile.TarInfo(name)
                    ti.size = size
                    tf.addfile(ti, io.BytesIO(data))
        return buf.getvalue()

    def test_differential_fuzz(self):
        rng = np.random.default_rng(0xF00D)
        for trial in range(24):
            fmt = tarfile.GNU_FORMAT if trial % 2 else tarfile.PAX_FORMAT
            raw = self._random_layer(rng, fmt)
            comp = "none" if trial % 5 == 0 else "lz4_block"
            accel = 1 if trial % 3 else 4
            opt = PackOption(
                chunk_size=0x4000, compressor=comp, lz4_acceleration=accel
            )
            blob_fast, res = pack_layer(raw, opt)
            out = io.BytesIO()
            pack_stream(out, io.BytesIO(raw), opt)
            assert blob_fast == out.getvalue(), f"trial {trial} diverged"
            if res.blob_size:
                tar_back = Unpack(
                    res.bootstrap, {res.blob_id: blob_data_from_layer_blob(blob_fast)}
                )
                with tarfile.open(fileobj=io.BytesIO(tar_back)) as tf:
                    names_back = {m.name.lstrip("./") for m in tf.getmembers()}
                with tarfile.open(fileobj=io.BytesIO(raw)) as tf:
                    in_members = tf.getmembers()
                    # every input member survives the round trip (dirs,
                    # symlinks, empties included; last-wins for dup paths)
                    assert {
                        m.name.lstrip("./").rstrip("/") for m in in_members
                    } <= names_back, f"trial {trial} lost members"
                    for m in in_members:
                        if m.isreg() and m.size > 0:
                            want = tf.extractfile(m).read()
                            with tarfile.open(fileobj=io.BytesIO(tar_back)) as tb:
                                got = tb.extractfile(
                                    next(x for x in tb.getmembers() if x.name.lstrip("./") == m.name.lstrip("./"))
                                ).read()
                            assert got == want, f"trial {trial}: {m.name}"
                            break  # one byte-check per trial keeps it fast

    def test_differential_with_chunk_dict(self):
        """Dict-enabled differential: both paths, packed against the same
        ChunkDict, stay byte-identical (dict hits skip storage in both)."""
        from nydus_snapshotter_tpu.converter.convert import Merge
        from nydus_snapshotter_tpu.converter.types import MergeOption
        from nydus_snapshotter_tpu.models.bootstrap import Bootstrap, ChunkDict

        rng = np.random.default_rng(0xD1C7)
        base = self._random_layer(rng, tarfile.GNU_FORMAT)
        opt = PackOption(chunk_size=0x4000)
        blob_a, _res_a = pack_layer(base, opt)
        merged = Merge([blob_a], MergeOption(with_tar=False))
        cdict = ChunkDict(Bootstrap.from_bytes(merged.bootstrap))
        # a fresh layer (misses) and the base itself (all dict hits)
        overlap = self._random_layer(rng, tarfile.GNU_FORMAT)
        for raw in (overlap, base):
            fast, res = pack_layer(raw, opt, chunk_dict=cdict)
            out = io.BytesIO()
            pack_stream(out, io.BytesIO(raw), opt, chunk_dict=cdict)
            assert fast == out.getvalue()


def _gnu_sparse_member() -> bytes:
    """Hand-crafted GNU sparse ('S') member: 8192-byte file, one 512-byte
    data region at offset 0 (tarfile can read but not write sparse)."""
    hdr = bytearray(512)
    hdr[0:10] = b"sparse.bin"
    hdr[100:108] = b"0000644\x00"
    hdr[108:116] = b"0000000\x00"
    hdr[116:124] = b"0000000\x00"
    hdr[124:136] = b"00000001000\x00"  # stored data: 512 bytes (octal)
    hdr[136:148] = b"00000000000\x00"
    hdr[156] = ord("S")
    hdr[257:265] = b"ustar  \x00"  # GNU magic
    hdr[386:398] = b"00000000000\x00"  # sparse[0].offset = 0
    hdr[398:410] = b"00000001000\x00"  # sparse[0].numbytes = 512
    hdr[483:495] = b"00000020000\x00"  # realsize = 8192 (octal)
    hdr[148:156] = b" " * 8
    hdr[148:156] = ("%06o\0 " % sum(hdr)).encode()
    return bytes(hdr) + b"\xab" * 512


class TestSparseMemberFusedGate:
    def test_sparse_plus_plan_files_identical_paths(self):
        """A layer mixing a sparse member (streams through the walk,
        seeding dedup/storage state) with normal files (planned) must
        stay byte-identical between the fast and streaming paths — the
        whole-layer fused lane must disable itself when the walk already
        seeded state."""
        rng = np.random.default_rng(31)
        norm = io.BytesIO()
        with tarfile.open(fileobj=norm, mode="w", format=tarfile.GNU_FORMAT) as tf:
            for i in range(4):
                data = rng.integers(0, 256, 90_000, dtype=np.uint8).tobytes()
                ti = tarfile.TarInfo(f"n{i}")
                ti.size = len(data)
                tf.addfile(ti, io.BytesIO(data))
        raw = _gnu_sparse_member() + norm.getvalue()
        # sanity: tarfile sees the sparse member with its real size
        with tarfile.open(fileobj=io.BytesIO(raw)) as tf:
            m0 = tf.getmembers()[0]
            assert m0.issparse() and m0.size == 8192
            content = tf.extractfile(m0).read()
            assert content == b"\xab" * 512 + b"\x00" * (8192 - 512)
        opt = PackOption(chunk_size=0x4000)
        blob_fast, res = pack_layer(raw, opt)
        out = io.BytesIO()
        pack_stream(out, io.BytesIO(raw), opt)
        assert blob_fast == out.getvalue()
        back = Unpack(
            res.bootstrap, {res.blob_id: blob_data_from_layer_blob(blob_fast)}
        )
        with tarfile.open(fileobj=io.BytesIO(back)) as tf:
            got = tf.extractfile("sparse.bin").read()
        assert got == content


class TestEarlyDeviceLaneStart:
    """pack_stream begins the device lane as soon as it has the layer in
    memory, before the dictionary and the scan; choose_lane still chooses.
    A begun lane that nothing finishes is closed: the pack is the one the
    host lanes give (or fails with their error), and nothing was counted."""

    CHUNK = 0x10000

    @staticmethod
    def _counts() -> dict:
        from nydus_snapshotter_tpu.ops import fused_convert

        disp, _bytes, _stages, fallbacks = fused_convert._counters()
        return {"dispatches": disp.value(), "early_starts": fused_convert._early_start_counter().value(),
                "host_fallbacks": fallbacks.value()}

    def _moved(self, before: dict) -> dict:
        return {k: v - before[k] for k, v in self._counts().items() if v != before[k]}

    @pytest.fixture
    def closed(self, monkeypatch):
        """The lanes that were begun in the test, to ask each whether it was closed."""
        from nydus_snapshotter_tpu.ops import fused_convert

        begun = []
        begin = fused_convert.FusedDeviceEngine.begin

        def spy(self, streams, stages):
            begun.append(begin(self, streams, stages))
            return begun[-1]

        monkeypatch.setattr(fused_convert.FusedDeviceEngine, "begin", spy)
        return begun

    def _layer(self, seed=53) -> bytes:
        """Four files; the first and the last are the same for every seed."""
        shared, own = np.random.default_rng(53), np.random.default_rng(seed)
        return build_tar([(f"e/f{i}", (shared if i in (0, 3) else own).integers(0, 256, size, dtype=np.uint8).tobytes())
                          for i, size in enumerate([300_000, 1_200, 450_000, 70_000])])

    def _pack(self, tar, backend, **kw):
        return pack_layer(tar, PackOption(chunk_size=self.CHUNK, backend=backend, **kw))

    def test_a_fused_pack_begins_early_and_finishes_once(self, closed):
        tar = self._layer()
        want, want_res = self._pack(tar, "hybrid")
        before = self._counts()
        got, got_res = self._pack(tar, "fused")
        assert got == want and got_res.bootstrap == want_res.bootstrap
        assert self._moved(before) == {"dispatches": 1, "early_starts": 1}
        (begun,) = closed  # one begin a pack: process_many did not begin again
        assert begun.table is None and begun.buffer_dev is None

    def test_a_tar_without_a_regular_file_drops_the_begun_lane(self, closed):
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w") as tf:
            for name, kind, link in [("d", tarfile.DIRTYPE, ""), ("d/e", tarfile.DIRTYPE, ""),
                                     ("d/l", tarfile.SYMTYPE, "e"), ("d/empty", tarfile.REGTYPE, "")]:
                info = tarfile.TarInfo(name)
                info.type, info.linkname = kind, link
                tf.addfile(info)
        tar = buf.getvalue()
        want, want_res = self._pack(tar, "hybrid")
        before = self._counts()
        got, got_res = self._pack(tar, "fused")
        assert got == want and got_res.bootstrap == want_res.bootstrap
        assert self._moved(before) == {}
        (begun,) = closed
        assert begun.n == len(tar) and begun.buffer_dev is None  # was enqueued, is closed

    def test_a_corrupt_tar_fails_as_on_the_host_lane(self, closed):
        from nydus_snapshotter_tpu.converter.types import ConvertError

        tar = bytearray(self._layer())
        tar[:512] = b"\xff" * 512  # the first member's header: no walk gets past it
        errors = {}
        before = self._counts()
        for backend in ("hybrid", "fused"):
            with pytest.raises(ConvertError) as e:
                self._pack(bytes(tar), backend)
            errors[backend] = str(e.value)
        assert errors["fused"] == errors["hybrid"]
        assert self._moved(before) == {}
        (begun,) = closed  # the hybrid pack begins nothing
        assert begun.n == len(tar) and begun.buffer_dev is None

    def test_another_lane_after_all_drops_the_begun_lane(self, closed, monkeypatch):
        from nydus_snapshotter_tpu.converter import stream

        tar = self._layer()
        want, want_res = self._pack(tar, "hybrid")
        ran = []

        def choose(option, **seen):
            ran.append(stream._lane_per_file)
            return stream._lane_per_file

        monkeypatch.setattr(stream, "choose_lane", choose)
        before = self._counts()
        got, got_res = self._pack(tar, "fused")
        assert got == want and got_res.bootstrap == want_res.bootstrap
        assert ran == [stream._lane_per_file] and self._moved(before) == {}
        (begun,) = closed
        assert begun.n == len(tar) and begun.buffer_dev is None

    @pytest.mark.parametrize("where", ["begin", "process_many", "the counts"])
    def test_a_planted_overflow_counts_one_fallback(self, closed, monkeypatch, where):
        from nydus_snapshotter_tpu.ops import fused_convert

        tar = self._layer()
        want, want_res = self._pack(tar, "hybrid")
        if where == "begin":  # no lane buffer holds the layer: begin and process_many both meet it
            def refuse(total, max_size):
                raise fused_convert.FusedOverflow("planted")

            monkeypatch.setattr(fused_convert, "padded_length", refuse)
        elif where == "process_many":  # benchmark/tests/test_faults.py's plant
            def overflow(self, streams, *a, **kw):
                raise fused_convert.FusedOverflow("planted")

            monkeypatch.setattr(fused_convert.FusedDeviceEngine, "process_many", overflow)
        else:
            monkeypatch.setattr(fused_convert, "_wcap_for", lambda n, bits, floor=1024: 2)
        before = self._counts()
        got, got_res = self._pack(tar, "fused")
        assert got == want and got_res.bootstrap == want_res.bootstrap
        assert self._moved(before) == {"host_fallbacks": 1}
        assert all(b.buffer_dev is None for b in closed) and len(closed) == (0 if where == "begin" else 1)

    @pytest.mark.parametrize("with_dict", [False, True])
    def test_cli_pack_fused_is_byte_identical_to_hybrid(self, tmp_path, with_dict):
        from nydus_snapshotter_tpu.cmd import convert as cli

        def run(*argv):
            assert cli.main(["--jax-platform", "cpu", *argv]) == 0

        (tmp_path / "a.tar").write_bytes(self._layer(53))
        (tmp_path / "b.tar").write_bytes(self._layer(54))
        extra = []
        if with_dict:  # image b shares two files with a
            run("pack", "--in", str(tmp_path / "b.tar"), "--out", str(tmp_path / "b.nydus"), "--backend", "hybrid",
                "--chunk-size", hex(self.CHUNK))
            run("merge", "--out", str(tmp_path / "b.boot"), str(tmp_path / "b.nydus"))
            extra = ["--chunk-dict", str(tmp_path / "b.boot")]
        blobs = {}
        before = self._counts()
        for backend in ("hybrid", "fused"):
            out = tmp_path / f"a.{backend}.nydus"
            run("pack", "--in", str(tmp_path / "a.tar"), "--out", str(out), "--backend", backend,
                "--chunk-size", hex(self.CHUNK), *extra)
            blobs[backend] = out.read_bytes()
        assert blobs["fused"] == blobs["hybrid"]
        assert self._moved(before) == {"dispatches": 1, "early_starts": 1}
