"""Streaming Pack: bounded-memory OCI-tar → nydus-blob conversion.

The reference streams a layer through 1 MiB FIFO buffers into the builder
process (pkg/converter/convert_unix.go:56-61,443-539) so conversion memory
is independent of layer size. This module is that discipline rebuilt around
the in-process engine:

    tar stream → per-file incremental CDC (bounded carry) → digest batches
    (device-dispatched double-buffered, or host thread pool) → dedup →
    compress/batch-pack → encrypt → dest

Nothing holds the whole layer: the chunker carries at most ``max_size`` of
lookahead per file, digests travel in fixed-budget batches (one in flight on
device while the host reads the next — JAX's async dispatch is the double
buffer), and blob bytes stream straight to ``dest`` because the nydus
framing puts each tar header *after* its data (models/nydus_tar.py). Only
metadata (inodes + chunk records) accumulates, O(files + chunks).

``converter.convert.Pack`` delegates here — this is the only Pack
implementation, so in-memory and streaming callers share one code path.

One pack is open → scan → lane → assemble → emit (``_pack_stream``). The
scan plans the in-memory members' extents, ``choose_lane`` picks what cuts
and digests them (a lane: a generator of one ``(cuts, digests)`` a planned
file), one loop slices the files by those cuts into the ordered dedup
(``_Assembler``), and ``emit_bootstrap`` builds the tables and the TOC.
"""

from __future__ import annotations

import hashlib
import io
import itertools
import math
import os
import stat
import tarfile
from contextlib import closing, nullcontext
from dataclasses import dataclass, field
from typing import BinaryIO, Optional

import numpy as np

from nydus_snapshotter_tpu import constants, trace
from nydus_snapshotter_tpu.converter import codec as codec_mod, crypto
from nydus_snapshotter_tpu.converter.convert import PackResult, ThreadSafeCompressor
from nydus_snapshotter_tpu.converter.convert import _make_compressor, match_prefetch_paths
from nydus_snapshotter_tpu.converter.types import ConvertError, PackOption
from nydus_snapshotter_tpu.models import fstree, layout, nydus_tar, toc
from nydus_snapshotter_tpu.models.bootstrap import (
    CHUNK_FLAG_BATCH,
    BatchRecord,
    BlobRecord,
    Bootstrap,
    ChunkDict,
    ChunkRecord,
    CipherRecord,
    Inode,
    parse_chunk_dict_arg,
)
from nydus_snapshotter_tpu.ops import cdc, native_cdc, sha256
from nydus_snapshotter_tpu.ops.chunker import ChunkDigestEngine, _pow2_ceil, host_digests_for

SEGMENT_BYTES = 4 << 20  # tar read granularity
DIGEST_BATCH_BYTES = 32 << 20  # chunk bytes per digest batch


class _CountingWriter:
    """Tracks the write position so ``dest`` needn't be seekable."""

    def __init__(self, f: BinaryIO):
        self.f = f
        self.pos = 0

    def write(self, b: bytes) -> int:
        self.f.write(b)
        self.pos += len(b)
        return len(b)

    def tell(self) -> int:
        return self.pos


class IncrementalChunker:
    """Per-file CDC with bounded carry.

    A FastCDC cut ending the chunk that starts at ``s`` depends only on
    bytes ``[s, s + max_size)``, so any cut whose chunk start has a full
    ``max_size`` of lookahead in the buffer is final; the rest is carried.
    Produces exactly the cuts a whole-stream run produces (ops/cdc.py
    resolution, native or numpy backend).
    """

    def __init__(self, opt: PackOption, engine=None):
        # One backend-selection policy: boundaries go through the engine
        # (jax = device two-phase candidates, hybrid = native, numpy = host).
        # Callers packing many files pass one shared engine instance.
        kwargs = {"digest_backend": opt.digest_backend} if opt.digest_backend else {}
        self._engine = engine or ChunkDigestEngine(
            chunk_size=opt.chunk_size,
            mode=opt.chunking,
            backend=opt.backend,
            digester=opt.digester,
            **kwargs,
        )
        self.params = self._engine.params  # None for fixed chunking
        self.lookahead = self.params.max_size if self.params else opt.chunk_size
        # Fused single-pass chunk+digest (native SIMD bitmaps + SHA-NI):
        # when the engine's fused arm is available, each drain yields
        # (chunk, digest) pairs directly — no separate digest sweep, no
        # per-chunk batching copies. Digests of carried-over chunks are
        # recomputed next drain (a few % of bytes at the drain cadence).
        self.fused = self._engine._fused_available()
        self._buf = bytearray()

    def feed(self, seg: bytes) -> list[tuple[bytes, Optional[bytes]]]:
        self._buf += seg
        if len(self._buf) < 2 * self.lookahead:
            return []
        return self._drain(final=False)

    def finish(self) -> list[tuple[bytes, Optional[bytes]]]:
        return self._drain(final=True)

    def _drain(self, final: bool) -> list[tuple[bytes, Optional[bytes]]]:
        buf = self._buf
        if not buf:
            return []
        # The engine converts bytes/bytearray via a shared-memory
        # frombuffer view — no copy; boundaries (and fused digests) are
        # computed before any mutation of the buffer.
        if self.fused:
            cuts, digests = native_cdc.chunk_digest_native(
                buf, self.params, digester=self._engine.digester
            )
        else:
            cuts, digests = self._engine.boundaries(buf), None
        out: list[tuple[bytes, Optional[bytes]]] = []
        s = 0
        for i, c in enumerate(cuts):
            c = int(c)
            if not final and s + self.lookahead > len(buf):
                break
            out.append(
                (
                    bytes(buf[s:c]),
                    digests[32 * i : 32 * (i + 1)] if digests is not None else None,
                )
            )
            s = c
        self._buf = bytearray(buf[s:]) if not final else bytearray()
        return out

    def cut_whole(self, arr: np.ndarray) -> "tuple[list[int], Optional[list[bytes]]]":
        """Cuts (exclusive ends) of a complete in-memory file, with its
        chunks' digests where the engine's fused arm gives them.

        The in-memory fast path: no bytearray accumulation, no byte of the
        caller's tar buffer copied (the reference avoids these copies by
        piping the raw stream straight into the builder process,
        pkg/converter/convert_unix.go:443-539).
        """
        if not self.fused:
            return self._engine.boundaries(arr).tolist(), None
        cuts, flat = native_cdc.chunk_digest_native(
            arr, self.params, digester=self._engine.digester
        )
        return cuts.tolist(), _split_digests(flat, 0, len(cuts))


def _split_digests(flat: bytes, start: int, n: int) -> list[bytes]:
    """Chunks ``start .. start + n`` of a native arm's back-to-back digests."""
    return [flat[32 * k : 32 * (k + 1)] for k in range(start, start + n)]


class _HostDigester:
    """Synchronous batch digests on the host.

    Chunks arrive as separate byte strings; packing them into one buffer
    + extent list lets the native SHA-NI arm digest the whole batch in a
    single GIL-dropping call with its pairwise chain interleaving —
    per-chunk calls would forfeit both. hashlib thread pool otherwise.
    """

    def __init__(self, digester: str = "sha256"):
        self.digester = digester

    def submit(self, datas: list[bytes]):
        # One shared buffer so the same-source-array grouping makes a
        # single native call for the whole batch.
        buf = np.frombuffer(b"".join(datas), dtype=np.uint8)
        items = []
        off = 0
        for d in datas:
            items.append((buf, off, len(d)))
            off += len(d)
        return host_digests_for(self.digester)(items)

    def collect(self, handle) -> list[bytes]:
        return handle


class _DeviceDigester:
    """Async device digests: submit dispatches (JAX async), collect blocks.

    Holding exactly one batch in flight while the host reads/chunks the next
    is the double-buffered infeed — device SHA-256 overlaps tar ingest.
    """

    def __init__(self, max_chunk: int):
        # Padded-block bucket clamp at the engine's true max chunk size
        # (a max-size chunk is one block over a power of two; rounding up
        # would double the scan — same reasoning as
        # ops/chunker._digests_bucketed).
        self._max_blocks = sha256.n_padded_blocks(max_chunk)

    def submit(self, datas: list[bytes]):
        import jax.numpy as jnp

        max_blocks = self._max_blocks
        buckets: dict[int, list[int]] = {}
        for i, d in enumerate(datas):
            nb = sha256.n_padded_blocks(len(d))
            cap = min(1 << (nb - 1).bit_length() if nb > 1 else 1, max_blocks)
            buckets.setdefault(cap, []).append(i)
        parts = []
        for cap, idxs in sorted(buckets.items()):
            blocks, counts = sha256.pack_messages_np([datas[i] for i in idxs], block_capacity=cap)
            m_pad = _pow2_ceil(len(idxs)) - len(idxs)
            if m_pad:
                blocks = np.concatenate([blocks, np.zeros((m_pad, cap, 16), np.uint32)])
                counts = np.concatenate([counts, np.zeros(m_pad, np.int32)])
            states = sha256.sha256_batch(jnp.asarray(blocks), jnp.asarray(counts))
            parts.append((idxs, states))
        return (len(datas), parts)

    def collect(self, handle) -> list[bytes]:
        import jax

        n, parts = handle
        out: list[Optional[bytes]] = [None] * n
        for idxs, states in parts:
            host = np.asarray(jax.device_get(states))
            for row, i in enumerate(idxs):
                out[i] = sha256.digest_to_bytes(host[row])
        return out  # type: ignore[return-value]


class _SectionWriter:
    """Streams the image.blob data section: alignment, batch packing,
    compression, encryption, hashing, extent accounting."""

    def __init__(self, out: _CountingWriter, opt: PackOption, compress):
        self.out = out
        self.compress = compress
        self.align = 4096 if (opt.aligned_chunk and opt.fs_version == layout.RAFS_V5) else 1
        self.batch_size = opt.batch_size
        self.hasher = hashlib.sha256()
        self.cipher: Optional[CipherRecord] = None
        self._encryptor = None
        if opt.encrypt:
            key, iv = crypto.generate_context()
            self.cipher = CipherRecord(algo=crypto.CIPHER_AES_256_CTR, key=key, iv=iv)
            self._encryptor = crypto.stream_encryptor(key, iv)
        self.coff = 0  # current offset within the data section
        self.extents: list[Optional[tuple[int, int, int]]] = []  # per unique chunk
        self.batches: list[tuple[int, int, int]] = []  # (coff, uncomp_base, usize)
        self._pending: list[tuple[int, bytes, int]] = []  # (uniq_idx, data, uoff)
        self._pending_bytes = 0

    def _write_raw(self, b: bytes) -> None:
        if self._encryptor is not None:
            b = self._encryptor.update(b)
        self.hasher.update(b)
        self.out.write(b)
        self.coff += len(b)

    def _emit(self, comp: bytes) -> int:
        pad = (-self.coff) % self.align
        if pad:
            self._write_raw(b"\x00" * pad)
        start = self.coff
        self._write_raw(comp)
        return start

    def _flush_batch(self) -> None:
        if not self._pending:
            return
        comp, cflag = self.compress(b"".join(d for _, d, _ in self._pending))
        start = self._emit(comp)
        for idx, _d, _u in self._pending:
            self.extents[idx] = (start, len(comp), cflag | CHUNK_FLAG_BATCH)
        self.batches.append((start, self._pending[0][2], self._pending_bytes))
        self._pending = []
        self._pending_bytes = 0

    def add(self, uniq_idx: int, data: bytes, uoff: int, precomp=None) -> None:
        assert uniq_idx == len(self.extents)
        self.extents.append(None)
        if self.batch_size and len(data) < self.batch_size:
            if self._pending_bytes + len(data) > self.batch_size:
                self._flush_batch()
            self._pending.append((uniq_idx, data, uoff))
            self._pending_bytes += len(data)
        else:
            self._flush_batch()
            # precomp: the chunk was compressed speculatively off-thread
            # (deterministic codec, same bytes as compressing here).
            comp, cflag = precomp if precomp is not None else self.compress(data)
            self.extents[uniq_idx] = (self._emit(comp), len(comp), cflag)

    def finish(self) -> None:
        self._flush_batch()
        if self._encryptor is not None:
            tail = self._encryptor.finalize()
            if tail:
                self.hasher.update(tail)
                self.out.write(tail)
                self.coff += len(tail)


class _SectionDigest:
    """hasher-shim over the digest the native pass computed."""

    def __init__(self) -> None:
        self._d = b""

    def digest(self) -> bytes:
        return self._d

    def hexdigest(self) -> str:
        return self._d.hex()


class _DeferredSectionWriter:
    """Blob data section assembled in ONE native pass at finish().

    During the walk, add() only records each unique chunk's source extent
    (zero-copy offsets into the caller's tar buffer; loose bytes go to a
    side buffer). finish() hands the whole extent list to
    ntpu_pack_section, which runs the per-chunk compress -> append loop
    and the section SHA-256 natively — the reference keeps this exact
    loop inside one `nydus-image create` process
    (pkg/converter/tool/builder.go:148-178), and re-entering Python per
    chunk was the dominant full-path overhead.

    Only used for layouts it reproduces byte-identically to
    _SectionWriter: chunks packed back-to-back (align 1, no batch
    packing), no encryption, lz4_block/zstd/none compressor (native zstd
    is ZSTD_compress level 3 — byte-identical to the Python lane's
    zstandard level-3 context against the same libzstd). If the native
    arm is unavailable at finish() (e.g. liblz4/libzstd vanished), the
    recorded extents replay through the Python codec — same bytes either
    way.
    """

    def __init__(self, out: _CountingWriter, opt: PackOption, compress, raw: memoryview):
        self.out = out
        self.compress = compress  # replay fallback only
        self.hasher = _SectionDigest()
        self.cipher = None
        self.coff = 0
        self.extents: list[Optional[tuple[int, int, int]]] = []
        self.batches: list[tuple[int, int, int]] = []
        self._kind = {"lz4_block": 1, "zstd": 2}.get(opt.compressor, 0)
        # codec-param slot: lz4 acceleration, or the zstd level (single
        # source constants.ZSTD_LEVEL — threads through to the native arm)
        self._accel = (
            constants.ZSTD_LEVEL if self._kind == 2 else opt.lz4_acceleration
        )
        self._cflag = {
            "lz4_block": constants.COMPRESSOR_LZ4_BLOCK,
            "zstd": constants.COMPRESSOR_ZSTD,
        }.get(opt.compressor, constants.COMPRESSOR_NONE)
        self._raw_arr = np.frombuffer(raw, dtype=np.uint8)
        self._base = self._raw_arr.ctypes.data
        self._raw_len = len(raw)
        self._items: list[tuple[int, int, int]] = []
        self._side = bytearray()

    def add(self, uniq_idx: int, data, uoff: int, precomp=None) -> None:
        assert uniq_idx == len(self._items)
        size = len(data)
        if isinstance(data, memoryview):
            off = np.frombuffer(data, dtype=np.uint8).ctypes.data - self._base
            if 0 <= off and off + size <= self._raw_len:
                self._items.append((0, off, size))
                return
            data = bytes(data)
        self._items.append((1, len(self._side), size))
        self._side += data

    def finish(self) -> None:
        m = len(self._items)
        if m == 0:
            return
        ext = np.asarray(self._items, dtype=np.int64)
        side = np.frombuffer(self._side, dtype=np.uint8) if self._side else np.empty(0, np.uint8)
        n_threads = _pack_threads()
        res = native_cdc.pack_section(
            self._raw_arr, side, ext, self._kind, self._accel, n_threads
        )
        if res is None:
            # Replay through the Python codec (identical bytes, slower).
            hasher = hashlib.sha256()
            for src, off, size in self._items:
                buf = (
                    self._raw_arr[off : off + size]
                    if src == 0
                    else side[off : off + size]
                )
                comp, cflag = self.compress(memoryview(buf))
                self.extents.append((self.coff, len(comp), cflag))
                hasher.update(comp)
                self.out.write(comp)
                self.coff += len(comp)
            self.hasher._d = hasher.digest()
            return
        self.adopt(*res)

    def adopt(self, blob, comp_extents, digest: bytes) -> None:
        """Adopt a native pass's assembled section: finish()'s own, or the
        whole-layer pass's (ntpu_pack_files, through _Assembler.adopt: it
        already compressed/assembled/hashed and nothing was ever add()ed,
        so the regular finish() stays a no-op)."""
        self.extents = [
            (int(comp_extents[j, 0]), int(comp_extents[j, 1]), self._cflag)
            for j in range(comp_extents.shape[0])
        ]
        self.hasher._d = digest
        if blob.size:
            self.out.write(memoryview(blob))
        self.coff = int(blob.size)


@dataclass
class _ChunkRef:
    """A file-extent's chunk before final record materialization."""

    digest: bytes
    size: int
    uniq_idx: int = -1  # index into the own-blob unique table
    dict_hit: Optional[ChunkRecord] = None


@dataclass
class _Meta:
    entry: fstree.FileEntry
    size: int = 0
    chunks: list[_ChunkRef] = field(default_factory=list)


def _pack_threads() -> int:
    """Worker count for the pack pipeline.

    ``NTPU_PACK_THREADS`` requests a count, but it auto-degrades to the
    core count: threads cannot help beyond the cores that exist, and the
    pooled pipeline measurably costs 13-23% over the fused single-thread
    lane when oversubscribed on one core (MULTICORE_r04). Tests that must
    exercise the threaded lanes regardless (the cross-lane byte-identity
    gate) set ``NTPU_PACK_THREADS_FORCE=1`` to bypass the clamp.
    """
    try:
        n = int(os.environ.get("NTPU_PACK_THREADS", ""))
    except ValueError:
        n = 0
    ncpu = os.cpu_count() or 1
    if n >= 1:
        if os.environ.get("NTPU_PACK_THREADS_FORCE", "") not in ("", "0"):
            return n
        return min(n, ncpu)
    return ncpu


def _tar_num(field: memoryview) -> int:
    """Tar numeric field: octal decoded inline (the ~100% case — int(_, 8)
    over the NUL-terminated, space-stripped text, exactly tarfile.nti's
    octal branch), GNU base-256 (lead byte 0x80/0xFF, e.g. >8 GiB sizes or
    pre-epoch mtimes) delegated to tarfile's decoder — one source of truth
    for the exotic branch; malformed fields raise ValueError so the fast
    scanner bails to tarfile."""
    b = bytes(field)
    if b and b[0] in (0x80, 0xFF):
        try:
            return tarfile.nti(b)
        except tarfile.InvalidHeaderError as e:
            raise ValueError(str(e)) from e
    end = b.find(0)
    s = (b if end < 0 else b[:end]).strip()
    if not s:
        return 0
    return int(s, 8)  # ValueError on garbage, as tarfile.nti raises


_TAR_PLAIN_TYPES = (b"0", b"\x00", b"1", b"2", b"3", b"4", b"5", b"6", b"7")


def _parse_pax_records(data: bytes) -> "dict[str, str] | None":
    """Decode a pax extended header block ("%d key=value\\n" records);
    None on malformed framing. Values decode utf-8/surrogateescape — the
    same round-trip tarfile uses, so binary xattrs survive."""
    out: dict[str, str] = {}
    pos = 0
    n = len(data)
    while pos < n:
        if data[pos] == 0:
            break  # zero padding after the last record
        sp = data.find(b" ", pos, pos + 20)
        if sp < 0:
            return None
        try:
            length = int(data[pos:sp])
        except ValueError:
            return None
        end = pos + length
        if length < sp - pos + 3 or end > n or data[end - 1] != 0x0A:
            return None
        eq = data.find(b"=", sp + 1, end)
        if eq < 0:
            return None
        key = data[sp + 1 : eq].decode("utf-8", "surrogateescape")
        out[key] = data[eq + 1 : end - 1].decode("utf-8", "surrogateescape")
        pos = end
    return out


def _fast_tar_members(raw: memoryview):
    """Header walk over an in-memory tar: [(TarInfo, data_offset)], or
    None when the archive needs tarfile's full machinery.

    tarfile.TarInfo.frombuf costs ~30 µs/member (field-by-field parse,
    encoding fallbacks) — ~20% of full-path convert on a node_modules-
    shaped layer. This scanner handles plain ustar/GNU members plus pax
    ``x`` extended headers (Go's archive/tar — the writer behind real
    docker layers — emits pax for xattrs/long names/big files) with
    checksum verification, and bails to tarfile for anything else: pax
    globals (g), GNU longname/longlink (L/K), sparse (S), non-ustar
    magic, truncated data, or a non-regular member carrying data. A None
    return loses nothing but the speedup.
    """
    out: list[tuple[tarfile.TarInfo, int]] = []
    pos = 0
    n = len(raw)
    saw_end = False
    pending_pax: "dict[str, str] | None" = None
    while pos + 512 <= n:
        hdr = raw[pos : pos + 512]
        hb = bytes(hdr)
        if hb[0] == 0:
            if hb.count(0) == 512:
                saw_end = True
                break  # end-of-archive
            return None
        if hb[257:263] not in (b"ustar\x00", b"ustar "):
            return None
        typ = hb[156:157]
        if typ not in _TAR_PLAIN_TYPES and typ != b"x":
            return None
        try:
            mode = _tar_num(hdr[100:108])
            uid = _tar_num(hdr[108:116])
            gid = _tar_num(hdr[116:124])
            size = _tar_num(hdr[124:136])
            mtime = _tar_num(hdr[136:148])
            chksum = _tar_num(hdr[148:156])
        except ValueError:
            return None
        if size < 0:
            # GNU base-256 can encode negative values; a negative size
            # would make the scan position stop advancing (infinite loop)
            # — bail and let tarfile reject the archive.
            return None
        if chksum != sum(hb) - sum(hb[148:156]) + 8 * 0x20:
            return None
        if typ == b"x":
            # pax extended header: records apply to the NEXT member.
            end = pos + 512 + size
            if end > n:
                return None
            pax = _parse_pax_records(bytes(raw[pos + 512 : end]))
            if pax is None:
                return None
            if any(k.startswith("GNU.sparse") for k in pax):
                # pax-sparse members need tarfile's sparse-map handling
                # (_proc_gnusparse_*): the data region is a packed map +
                # holes, not the file bytes.
                return None
            pending_pax = pax
            pos = pos + 512 + 512 * ((size + 511) // 512)
            continue
        if typ not in (b"0", b"\x00", b"7"):
            if size != 0:
                return None  # non-regular member carrying data: exotic
            data_size = 0
        else:
            data_size = size
        name = hb[:100].split(b"\x00", 1)[0].decode("utf-8", "surrogateescape")
        if hb[257:263] == b"ustar\x00":
            prefix = hb[345:500].split(b"\x00", 1)[0]
            if prefix:
                name = prefix.decode("utf-8", "surrogateescape") + "/" + name
        # tarfile semantics: a trailing slash marks a directory (even with
        # a regular typeflag) and is stripped from the stored name.
        if name.endswith("/"):
            if typ in (b"0", b"\x00"):
                typ = b"5"
            name = name.rstrip("/")
        ti = tarfile.TarInfo(name)
        ti.mode = mode
        ti.uid = uid
        ti.gid = gid
        ti.size = size
        ti.mtime = mtime
        ti.type = typ
        ti.linkname = hb[157:257].split(b"\x00", 1)[0].decode(
            "utf-8", "surrogateescape"
        )
        if typ in (b"3", b"4"):
            try:
                ti.devmajor = _tar_num(hdr[329:337])
                ti.devminor = _tar_num(hdr[337:345])
            except ValueError:
                return None  # malformed device numbers: let tarfile decide
        if pending_pax is not None:
            # Apply overrides exactly as tarfile._apply_pax_info does for
            # the fields this pipeline consumes.
            p = pending_pax
            try:
                if "path" in p:
                    # tarfile._apply_pax_info only rstrips; it never
                    # retypes on a trailing slash (that V7 rule applies to
                    # base-header names only).
                    ti.name = p["path"].rstrip("/")
                if "linkpath" in p:
                    ti.linkname = p["linkpath"]
                if "size" in p:
                    ti.size = int(p["size"])
                    if ti.size < 0:
                        # Bailing to tarfile is NOT safe here: tarfile
                        # walks backwards off the member and silently
                        # yields nothing more — a data-losing "valid"
                        # image. Reject outright.
                        raise ConvertError(
                            f"bad layer tar: negative pax size for {ti.name!r}"
                        )
                    if typ in (b"0", b"\x00", b"7"):
                        data_size = ti.size
                if "mtime" in p:
                    ti.mtime = float(p["mtime"])
                    if not math.isfinite(ti.mtime):
                        # nan/inf would escape later as a bare ValueError
                        # from int(mtime); bail to tarfile instead.
                        return None
                if "uid" in p:
                    ti.uid = int(p["uid"])
                if "gid" in p:
                    ti.gid = int(p["gid"])
            except ValueError:
                return None
            ti.pax_headers = p
            pending_pax = None
        data_off = pos + 512
        pos = data_off + 512 * ((data_size + 511) // 512)
        if pos > n:
            return None  # truncated member data: let tarfile raise
        out.append((ti, data_off))
    # Without the end-of-archive zero block the input is truncated or not
    # a tar at all (e.g. a few garbage bytes) — bail so tarfile raises the
    # proper error instead of silently converting to an empty image.
    return out if saw_end else None


class _Assembler:
    """The ordered dedup of one pack: first wins over the blob's own chunks
    and the dictionary's, in tar order (deterministic); a chunk seen for
    the first time goes to the section writer."""

    def __init__(self, section, chunk_dict):
        self.section = section
        self.chunk_dict = chunk_dict
        self.own: dict[bytes, int] = {}  # digest -> index among the blob's unique chunks
        self.uncomp_offsets: list[int] = []  # per unique chunk
        self.uoff = 0
        self.dict_hits: dict[bytes, ChunkRecord] = {}
        self.dict_blobs_used: list[str] = []
        # frames the stage pipeline compressed ahead, by digest (its lane
        # sets it for its run)
        self.comp = None

    def process(self, batch: list[tuple[_Meta, bytes]], digests: list[bytes]) -> None:
        chunk_dict, own, dict_hits, comp = self.chunk_dict, self.own, self.dict_hits, self.comp
        for (meta, data), digest in zip(batch, digests):
            ref = _ChunkRef(digest=digest, size=len(data))
            if chunk_dict is not None and digest not in dict_hits and digest not in own:
                hit = chunk_dict.get(digest)
                if hit is not None:
                    dict_hits[digest] = hit
                    bid = chunk_dict.blob_id_for(hit)
                    if bid not in self.dict_blobs_used:
                        self.dict_blobs_used.append(bid)
            if digest in dict_hits:
                ref.dict_hit = dict_hits[digest]
            else:
                idx = own.get(digest)
                if idx is None:
                    idx = own[digest] = len(self.uncomp_offsets)
                    self.uncomp_offsets.append(self.uoff)
                    self.section.add(
                        idx,
                        data,
                        self.uoff,
                        # pop: each unique digest reaches here exactly once;
                        # releasing the entry keeps peak RSS at one chunk,
                        # not the whole compressed blob.
                        precomp=comp.pop(digest, None) if comp else None,
                    )
                    self.uoff += len(data)
                ref.uniq_idx = idx
            meta.chunks.append(ref)

    def adopt(self, plan, fused: dict) -> None:
        """Take the whole-layer native pass's result whole (only into a
        state no chunk has seeded): its dedup decisions, its section."""
        digs = fused["digests"]
        sizes, uniq = fused["chunk_sizes"].tolist(), fused["chunk_uniq"].tolist()
        pos = 0
        for (meta, _off, _size), nc in zip(plan, fused["file_nchunks"].tolist()):
            end = pos + nc
            meta.chunks.extend(map(_ChunkRef, _split_digests(digs, pos, nc), sizes[pos:end], uniq[pos:end]))
            pos = end
        usz = fused["uniq_sizes"]
        if len(usz):
            self.uncomp_offsets = (
                np.concatenate([[0], np.cumsum(usz[:-1])]).astype(np.int64).tolist()
            )
            self.uoff = int(usz.sum())
        self.section.adopt(fused["blob"], fused["comp_extents"], fused["blob_digest"])


class _DigestQueue:
    """Chunks that reach the pack without a digest, in batches of
    DIGEST_BATCH_BYTES with one batch in flight (on the device while the
    host reads on); digested batches go to the assembler in order."""

    def __init__(self, digester, asm: _Assembler):
        self.digester = digester
        self.asm = asm
        self.pending: list[tuple[_Meta, bytes]] = []
        self.pending_bytes = 0
        self.in_flight: Optional[tuple[object, list[tuple[_Meta, bytes]]]] = None

    def add(self, meta: _Meta, data: bytes, digest: Optional[bytes] = None) -> None:
        if digest is not None:
            # the fused chunker already digested this chunk (cache-warm,
            # single native pass); dedup/write it immediately, in order
            self.asm.process([(meta, data)], [digest])
            return
        self.pending.append((meta, data))
        self.pending_bytes += len(data)
        if self.pending_bytes >= DIGEST_BATCH_BYTES:
            self.dispatch()

    def dispatch(self) -> None:
        if self.in_flight is not None:
            handle, batch = self.in_flight
            self.asm.process(batch, self.digester.collect(handle))
            self.in_flight = None
        if self.pending:
            self.in_flight = (self.digester.submit([d for _, d in self.pending]), self.pending)
            self.pending = []
            self.pending_bytes = 0

    def drain(self) -> None:
        self.dispatch()  # collects old, dispatches remainder
        self.dispatch()  # collects remainder


@dataclass
class _Pack:
    """What one pack's stages share: made at its open, handed to its lane."""

    opt: PackOption
    chunker: IncrementalChunker
    asm: _Assembler
    queue: _DigestQueue
    threads: int
    codec: object  # an active converter.codec.AdaptiveCodec, or None
    budget: object
    stats: Optional[dict]
    begun: object = None  # the device lane's first half, where the pack began it (_begin_device_lane)


# ---------------------------------------------------------------------------
# Lanes: what cuts and digests a pack's planned files
# ---------------------------------------------------------------------------
#
# A lane is a generator function (pack, plan, arr, stages): ``plan`` the
# scan's [(meta, offset, size)] in tar order, ``arr`` the whole tar as
# u8[n], ``stages`` the pack's running trace.Stages, on which the lane
# opens its own leaf spans. It yields, per planned file and in plan order,
# (cuts, digests): the file's exclusive chunk ends and its chunks' digests,
# or None for digests the lane leaves to the digest queue. Before its first
# result it may raise _LaneDeclined (one file overflows the device lane's
# buffer, a native arm lacks its codec library).


class _LaneDeclined(Exception):
    """This lane cannot run this plan after all: choose_lane names the next."""


def _extents(plan) -> list[tuple[int, int]]:
    return [(off, size) for _meta, off, size in plan]


def _lane_native_whole(pack: _Pack, plan, arr, stages):
    """Every planned file through ONE native call; the assembler adopts
    the result whole."""
    # Chunk + digest + first-wins dedup + compress + assemble + blob hash
    # (the reference's entire `nydus-image create` hot loop). The pass owns
    # the WHOLE dedup/storage state or none, so no file is left to feed.
    section = pack.asm.section
    stages.next("pack:fused_pack")
    fused = native_cdc.pack_files(
        arr, np.asarray(_extents(plan), dtype=np.int64), pack.chunker.params,
        section._kind, section._accel, pack.threads, digester=pack.opt.digester,
    )
    if fused is None:
        raise _LaneDeclined("ntpu_pack_files cannot run")
    pack.asm.adopt(plan, fused)
    yield from itertools.repeat(((), ()), len(plan))  # every file's chunks are in


def _lane_native_multi(pack: _Pack, plan, arr, stages):
    """ONE native call fuses chunk+digest for EVERY planned file."""
    # Small and large alike — a <= min_size file is exactly one CDC chunk,
    # so the unified pass subsumes the batched small-file digest sweep. Cut
    # points and digests are bit-identical to the per-file lane's.
    stages.next("pack:chunk_digest")
    ncuts, cuts, digs = native_cdc.chunk_digest_multi(
        arr, np.asarray(_extents(plan), dtype=np.int64), pack.chunker.params,
        digester=pack.opt.digester,
    )
    stages.next("pack:dedup")
    cuts = cuts.tolist()
    pos = 0
    for nc in ncuts.tolist():
        yield cuts[pos : pos + nc], _split_digests(digs, pos, nc)
        pos += nc


def _device_engine(opt: PackOption):
    from nydus_snapshotter_tpu.ops import fused_convert

    return fused_convert.FusedDeviceEngine(chunk_size=opt.chunk_size, digester=opt.digester)


def _begin_device_lane(opt: PackOption, arr, stages):
    """The device lane's first half (FusedDeviceEngine.begin: the tar's
    upload and pass 1 enqueued, nothing waited for) as soon as the pack
    has the layer in memory, so that the device works while the host
    parses the dictionary and walks the tar; None for a pack that does not
    lead there, by read_layer's own condition, and for a layer that no one
    lane buffer holds: its file table decides its batches, so _lane_device
    begins them after the scan. choose_lane still chooses: the pack closes
    a begun lane that was never finished."""
    if not (arr is not None and arr.size and _device_lane_wanted(opt)):
        return None
    from nydus_snapshotter_tpu.ops import fused_convert

    engine = _device_engine(opt)
    if not fused_convert.lane_fits(arr.size, engine.params.max_size):
        return None
    return engine.begin(arr, stages)


def _lane_device(pack: _Pack, plan, arr, stages):
    """Every planned file through the device engine, which drives
    ``pack:lane.*`` on ``stages``: the WHOLE layer as one two-dispatch
    batch where one lane buffer holds it (the pack began it when it read
    the layer), else as batches of whole files, each two dispatches, the
    next one's upload and pass 1 under this one's host work
    (FusedDeviceEngine.process_batches). Yields once all are in: a batch
    is not a stretch of the plan's order."""
    # ops/fused_convert — gear+compaction, then gather+digest, the host
    # keeping only cut metadata: the tar is the lane's buffer, the plan's
    # extents its table.
    from nydus_snapshotter_tpu.ops import fused_convert

    try:
        res = _device_engine(pack.opt).process_batches(
            fused_convert.Extents(arr, _extents(plan)), stages=stages, begun=pack.begun
        )
    except fused_convert.FusedOverflow as e:  # one file past the limit, pathological input
        fused_convert.record_host_fallback()
        raise _LaneDeclined(str(e)) from e
    stages.next("pack:dedup")
    yield from zip(res.cuts, res.digests)


def _file_pipeline(pack: _Pack, plan, arr, file_idxs: list[int]):
    """The stage-parallel pipeline (parallel/pipeline.py) over the planned
    files ``file_idxs``, or None where its configuration is off or there
    is nothing to overlap."""
    # Within-layer parallelism for multi-core hosts (the reference gets it
    # from the builder's internal thread pool): workers chunk + digest
    # files and speculatively compress each unique chunk as soon as its
    # digest exists — compression is deterministic, so racing duplicate
    # digests write identical bytes — and the ordered walk only dedups +
    # assembles. Queues between stages are byte-bounded and compressed
    # bytes in flight draw from a MemoryBudget (shared across layers in
    # batch conversion), so convert memory stays independent of layer size
    # and count. Blob bytes are identical to the serial path (pinned by
    # tests/test_fast_tar.py and tests/test_pipeline_determinism.py).
    from nydus_snapshotter_tpu.parallel import pipeline as pipeline_mod

    opt, chunker, chunk_dict = pack.opt, pack.chunker, pack.asm.chunk_dict
    pcfg = pipeline_mod.resolve_config(pack.threads) if len(file_idxs) > 1 else None
    if pcfg is None or not pcfg.enabled:
        return None
    raw = memoryview(arr)
    # Non-fused engines cut without digesting; digest in the worker (same
    # bytes → same digests as the batched host dispatch) so dedup and
    # speculative compression can run ahead of the ordered walk.
    digest_fn = None if chunker.fused else host_digests_for(opt.digester)

    def chunk_one(i: int):
        _meta, off, size = plan[i]
        cuts, digests = chunker.cut_whole(arr[off : off + size])
        starts = [0, *cuts[:-1]]
        if digests is None:
            digests = digest_fn([(arr, off + s, c - s) for s, c in zip(starts, cuts)])
        return [(raw[off + s : off + c], d) for s, c, d in zip(starts, cuts, digests)]

    compress_fn = compress_eligible = None
    if opt.compressor in ("lz4_block", "zstd") and not isinstance(
        pack.asm.section, _DeferredSectionWriter
    ):
        # (Deferred sections compress inside the native pass with their
        # own thread fan-out — speculating here would do the work twice.)
        # Per-thread codec contexts: lz4 calls are stateless, zstd contexts
        # are not thread-safe; both codecs are deterministic.
        # ThreadSafeCompressor also carries the encode_many batch seam:
        # pipeline compress workers drain up to [compression] batch_chunks
        # queued chunks into one GIL-released native batch-encode call
        # (byte-identical frames either way).
        compress_fn = ThreadSafeCompressor(opt.compressor, opt.lz4_acceleration, codec=pack.codec)

        def compress_eligible(digest, view):
            if opt.batch_size and len(view) < opt.batch_size:
                return False  # batch-packed: compressed jointly
            if chunk_dict is not None and chunk_dict.get(digest):
                return False  # dict hit: never stored
            return True

    return pipeline_mod.ConvertPipeline(
        items=[(i, plan[i][2]) for i in file_idxs],
        chunk_fn=chunk_one,
        compress_fn=compress_fn,
        compress_eligible=compress_eligible,
        config=pcfg,
        budget=pack.budget,
        stats=pack.stats,
    )


def _lane_per_file(pack: _Pack, plan, arr, stages, workers: bool = False):
    """File by file in tar order, chunking here or (``workers``) ahead on
    the stage pipeline's threads."""
    chunker, asm = pack.chunker, pack.asm
    # chunking interleaves with the driver's ordered dedup walk file by
    # file, so the loop is ONE span, never one a file
    stages.next("pack:chunk_digest")
    # Where the chunker's native arm digests, the files of one chunk
    # (≤ min_size — the node_modules shape) are digested in a single native
    # SHA sweep over the tar buffer instead of one engine call per file.
    small_max = chunker.params.min_size if chunker.fused else 0
    small_items = [(arr, off, size) for _m, off, size in plan if size <= small_max]
    small_digests = iter(host_digests_for(pack.opt.digester)(small_items)) if small_items else None
    files = [i for i, (_m, _o, size) in enumerate(plan) if size > small_max]
    pipe = _file_pipeline(pack, plan, arr, files) if workers else None
    if pipe is not None:
        # Serial-path equivalence: any walk-time chunks (sparse members)
        # sit in the pending digest batches and would be section.add'ed
        # before the plan's chunks — drain them now so the pipelined
        # immediate process() keeps that order.
        pack.queue.drain()
        if pipe.compress_fn is not None:
            asm.comp = pipe.comp
    try:
        with pipe if pipe is not None else nullcontext():
            for i, (_meta, off, size) in enumerate(plan):
                if size <= small_max:  # exactly one chunk
                    yield [size], [next(small_digests)]
                elif pipe is None:
                    yield chunker.cut_whole(arr[off : off + size])
                else:
                    chunks = pipe.chunks_for(i)
                    cuts = itertools.accumulate(len(view) for view, _d in chunks)
                    yield cuts, [d for _v, d in chunks]  # the workers digest every chunk
    finally:
        asm.comp = None


def _lane_per_file_workers(pack: _Pack, plan, arr, stages):
    """The per-file lane, the stage pipeline's threads chunking ahead of it."""
    return _lane_per_file(pack, plan, arr, stages, workers=True)


def _device_lane_wanted(opt: PackOption) -> bool:
    """The device lane's entry condition: choose_lane asks it of a scanned
    pack, read_layer of a layer it is about to read."""
    return opt.backend == "fused" and opt.chunking == "cdc"


def _deferred_section(opt: PackOption, codec_active: bool, native) -> bool:
    """Whether an in-memory layer's data section is assembled in one native
    pass (_DeferredSectionWriter): only for layouts it reproduces byte for byte."""
    return (
        opt.compressor in ("none", "lz4_block", "zstd")
        # the adaptive codec owns per-chunk frame decisions — the native
        # section arms compress at one fixed level and would bypass it
        and not codec_active
        and not opt.encrypt
        and not opt.batch_size
        and not (opt.aligned_chunk and opt.fs_version == layout.RAFS_V5)
        and native.pack_section_available()
    )


def choose_lane(
    opt: PackOption,
    *,
    in_memory: bool,
    threads: int,
    host_fused: bool,
    has_dict: bool,
    codec_active: bool,
    seeded: bool,
    native=native_cdc,
    declined=(),
):
    """The first lane, in order of precedence (native whole-layer, native
    multi, device, per-file with workers, per-file), that this pack can
    take and that has not ``declined`` its plan; None for a source that is
    not in memory (it plans nothing: the walk chunked each member as it
    streamed). Judged from what the pack can observe and nothing else:
    ``opt``, the pack ``threads`` (_pack_threads), whether the chunker's
    native chunk+digest arm serves this ``opt`` (``host_fused``:
    IncrementalChunker.fused) and which further arms of ``native`` loaded,
    a dictionary, an active adaptive codec, and whether the walk already
    ``seeded`` chunk state (sparse members are chunked as it goes)."""
    if not in_memory:
        return None
    # the native arms serve one thread, and CDC on the hybrid backend only
    # (host_fused), so a --backend fused pack takes neither of them
    multi = threads == 1 and host_fused and native.chunk_digest_multi_available()
    lanes = (
        (
            _lane_native_whole,
            multi
            # dict probes stay in the Python dedup lane
            and not has_dict
            # the pass owns the WHOLE dedup/storage state or none
            and not seeded
            and _deferred_section(opt, codec_active, native)
            and native.pack_files_available(),
        ),
        (_lane_native_multi, multi),
        (_lane_device, _device_lane_wanted(opt)),
        # Host arms only: fused/native/numpy chunking is safe to call from
        # worker threads (GIL-dropping where it matters); the jax lanes
        # keep their own double-buffered device dispatch discipline.
        (
            _lane_per_file_workers,
            threads > 1 and opt.backend in ("hybrid", "numpy") and opt.digest_backend != "jax",
        ),
        (_lane_per_file, True),
    )
    return next(lane for lane, open_ in lanes if open_ and lane not in declined)


def read_layer(f: BinaryIO, opt: PackOption):
    """The whole layer tar of the open file ``f``, for pack_stream. Where
    ``opt`` leads to the device lane it is read into the head of a zeroed
    buffer of the lane's padded length, so that the lane uploads the
    buffer as it stands (fused_convert.lane_buffer; the untouched tail
    costs no page), and a view of the tar's own bytes is returned; a
    layer that no one lane buffer holds into a page-aligned buffer of its
    own length, runs of which the lane uploads as its batches. Any other
    pack gets the plain ``bytes``."""
    size = os.fstat(f.fileno()).st_size  # 0 for a pipe
    if not (size and not opt.oci_ref and _device_lane_wanted(opt)):
        return f.read()
    from nydus_snapshotter_tpu.ops import fused_convert

    opt.validate()  # the chunk size, before the padding rule takes it
    try:
        npad = fused_convert.padded_length(size, cdc.CDCParams(opt.chunk_size).max_size)
    except fused_convert.FusedOverflow:
        npad = size  # it goes up in batches, each padded on the device
    buf = fused_convert.zeroed_buffer(npad)
    view = memoryview(buf)[:size]
    got = 0
    while got < size:
        k = f.readinto(view[got:])
        if not k:
            break
        got += k
    rest = f.read()
    if got == size and not rest:
        return buf[:size]
    return bytes(view[:got]) + rest  # the file changed under the read


# ---------------------------------------------------------------------------
# The pack
# ---------------------------------------------------------------------------


def pack_stream(
    dest: BinaryIO,
    src_tar: "BinaryIO | bytes | np.ndarray",
    opt: PackOption,
    chunk_dict=None,
    stats: "Optional[dict]" = None,
    budget=None,
    codec=None,
):
    """Stream one OCI layer tar into a nydus blob written to ``dest``.

    ``src_tar``: a file-like source, or the whole tar in memory as
    ``bytes`` / ``bytearray`` / a 1-D uint8 array (best what ``read_layer``
    gives: for the device lane the head of a ``fused_convert.zeroed_buffer``
    of ``padded_length`` bytes, which the lane then uploads without a copy).

    Reference semantics (convert_unix.go:325-539): uncompressed layer tar
    in, tar-like nydus blob out; chunk-dict hits are referenced, not stored.
    ``chunk_dict`` passes an already-loaded dict object (anything with the
    ChunkDict get/blob_id_for/bootstrap interface) so batch conversion can
    reuse one growing dict without re-parsing a bootstrap per layer;
    ``opt.chunk_dict_path`` is the file-based fallback.

    The pack's wall is a flat partition into consecutive leaf spans under
    a ``convert.pack`` root (docs/observability.md): ``pack:dict_load``,
    ``pack:scan``, then the lane's (``pack:lane.*`` from the fused device
    engine, or one ``pack:chunk_digest`` / ``pack:fused_pack``),
    ``pack:dedup``, ``pack:compress_write``, ``pack:bootstrap``. A pack
    that leads to the device lane begins it first: ``pack:lane.layout``,
    ``.h2d`` and a first ``.pass1`` (the upload and pass 1 enqueued) come
    before ``pack:dict_load``, the wait for them after ``pack:scan``; a
    layer that no one lane buffer holds brings all of ``pack:lane.*`` once
    a batch, after ``pack:scan``.

    ``stats``: optional dict that accumulates per-stage wall seconds, the
    sums of those spans' own times (``_STATS_SPANS``): ``scan`` tar walk +
    metadata, ``chunk_digest`` CDC + chunk digests (on the per-file lanes
    the ordered dedup walk interleaves with it and is inside),
    ``fused_pack`` the whole-layer native pass, ``dedup``
    dedup/bookkeeping, ``assemble`` compression + blob append + blob
    digest, ``bootstrap`` inode/chunk-table serialization, ``dict_load``.

    ``budget``: optional :class:`parallel.pipeline.MemoryBudget` bounding
    this conversion's speculative-compression bytes in flight; batch
    conversion passes ONE budget for every concurrently packing layer so
    aggregate convert memory stays independent of layer count. ``None``
    draws from the process-wide shared budget.

    ``codec``: optional :class:`converter.codec.AdaptiveCodec` — the
    adaptive per-chunk zstd engine (probe/bypass/per-class levels/
    trained dict). ``None`` resolves it from config/env; when the engine
    is off (the default) the pack keeps the fixed-level lane and its
    byte-identity invariant, including the native deferred/fused section
    arms. An ACTIVE codec owns the chunk-frame decisions, so the pack
    routes through the Python section writer (the codec-stage interface
    a device-offloaded codec would implement too).
    """
    stages = trace.Stages()
    try:
        with trace.batch_span("convert.pack"), stages:
            return _pack_stream(
                dest, src_tar, opt, chunk_dict, stats, budget, codec, stages
            )
    finally:
        if stats is not None:
            for key, names in _STATS_SPANS.items():
                stats[key] = stats.get(key, 0.0) + sum(
                    v for k, v in stages.seconds.items() if k.startswith(names)
                )


# pack_stream's ``stats`` keys <- the leaf spans (name prefixes) they sum
_STATS_SPANS = {
    "dict_load": ("pack:dict_load",),
    "scan": ("pack:scan",),
    "chunk_digest": ("pack:chunk_digest", "pack:lane."),
    "fused_pack": ("pack:fused_pack",),
    "dedup": ("pack:dedup",),
    "assemble": ("pack:compress_write",),
    "bootstrap": ("pack:bootstrap",),
}


def _scan(src_tar, raw: Optional[memoryview], opt: PackOption, queue: _DigestQueue, chunker):
    """The tar walk -> (metas by path, opaque dirs, the plan [(meta,
    offset, size)] of the in-memory members' extents, members seen)."""
    # An in-memory layer (``raw``) takes the zero-copy path: random-access
    # tar parse, and chunk/digest work deferred to the lane — the plan stays
    # in tar order, so the blob layout and dedup state are identical to
    # immediate processing. A member of a file-like source (and a sparse
    # one) is chunked here as it streams, bounded-memory: that discipline
    # only matters for sources that may not fit in RAM.
    metas: dict[str, _Meta] = {}
    opaque_dirs: list[str] = []
    plan: list[tuple[_Meta, int, int]] = []

    def walk(info, data_off, tf) -> None:
        path = fstree.norm_path(info.name)
        special = fstree.classify_special(path)
        if special is not None:
            kind, target = special
            if kind == "opaque":
                opaque_dirs.append(target)
            else:
                metas[target] = _Meta(entry=fstree.whiteout_entry(target))
            return
        entry = fstree.entry_from_tarinfo(tf, info, path, with_data=False)
        meta = _Meta(entry=entry)
        # A path repeated in the tar: last entry wins (as in a real
        # extraction); chunks already written for the earlier one stay in
        # the blob as dead bytes.
        metas[path] = meta
        if not (entry.is_regular and info.size > 0):
            return
        meta.size = info.size
        if data_off is not None and not getattr(info, "sparse", None):
            # Zero-copy: the member's bytes are a slice of the caller's
            # buffer (sparse members store data compacted, so they take
            # the extractfile path).
            plan.append((meta, data_off, info.size))
            return
        f = tf.extractfile(info)
        if f is None:
            raise ConvertError(f"tar member {path!r} has no data stream")
        member = IncrementalChunker(opt, engine=chunker._engine)
        while True:
            seg = f.read(SEGMENT_BYTES)
            if not seg:
                break
            for chunk, digest in member.feed(seg):
                queue.add(meta, chunk, digest)
        for chunk, digest in member.finish():
            queue.add(meta, chunk, digest)

    members = _fast_tar_members(raw) if raw is not None else None
    if members is not None:
        for info, data_off in members:
            walk(info, data_off, None)  # tf unused: data via raw
        return metas, opaque_dirs, plan, len(members)
    n_members = 0
    try:
        # Random access for in-memory layers (tarfile's stream mode copies
        # every data byte through its internal block buffers). io.BytesIO
        # shares a bytes object and copies anything else, so it is built
        # only here, where the fast walk gave up.
        with tarfile.open(
            fileobj=io.BytesIO(src_tar) if raw is not None else src_tar,
            mode="r:" if raw is not None else "r|",
        ) as tf:
            for info in tf:
                n_members += 1
                walk(info, info.offset_data if raw is not None else None, tf)
    except tarfile.TarError as e:
        raise ConvertError(f"bad layer tar: {e}") from e
    return metas, opaque_dirs, plan, n_members


def _pack_stream(dest, src_tar, opt, chunk_dict, stats, budget, codec, stages):
    """pack_stream's body: open, scan, lane, assemble, emit; ``stages``
    (trace.Stages) runs its leaf spans."""
    opt.validate()
    # A uint8 array counts as an in-memory layer too (read_layer hands one
    # over with the device lane's padding behind it, and the lane uploads
    # that as it is).
    raw: Optional[memoryview] = None
    arr = None  # the same bytes as u8[n]
    if isinstance(src_tar, (bytes, bytearray, np.ndarray)):
        if isinstance(src_tar, np.ndarray) and not (
            src_tar.dtype == np.uint8 and src_tar.ndim == 1 and src_tar.flags.c_contiguous
        ):
            raise ConvertError("an in-memory layer tar array must be contiguous 1-D uint8")
        raw = memoryview(src_tar)
        # the caller's own array where it gave one: the device lane looks
        # for room behind it (fused_convert.lane_buffer)
        arr = src_tar if isinstance(src_tar, np.ndarray) else np.frombuffer(raw, dtype=np.uint8)

    # a pack that leads to the device lane opens pack:lane.{layout,h2d,pass1}
    # here, ahead of its own host work; the lane's other leaves follow the scan
    begun = _begin_device_lane(opt, arr, stages)
    try:
        if chunk_dict is None and opt.chunk_dict_path:
            # service://<uds>[#namespace] connects a shared-dict mirror; any
            # other shape is the file-based dict as before.
            from nydus_snapshotter_tpu.parallel.dict_service import open_chunk_dict

            stages.next("pack:dict_load")
            chunk_dict = open_chunk_dict(opt.chunk_dict_path)
            stages.annotate(
                dict_chunks=len(chunk_dict), dict_blobs=len(chunk_dict.blob_ids())
            )
        # everything up to the lane's own call is the scan: set-up, the member
        # walk, the plan's extents
        stages.next("pack:scan")
        if codec is None:
            codec = codec_mod.resolve_codec(opt)
        out = _CountingWriter(dest)
        compress = _make_compressor(opt.compressor, opt.lz4_acceleration, codec=codec)
        if raw is not None and _deferred_section(opt, codec is not None, native_cdc):
            section: "object" = _DeferredSectionWriter(out, opt, compress, raw)
        else:
            section = _SectionWriter(out, opt, compress)
        chunker = IncrementalChunker(opt)
        asm = _Assembler(section, chunk_dict)
        queue = _DigestQueue(
            _DeviceDigester(chunker.lookahead)
            # the device batch kernel is SHA-256; blake3 always digests on the
            # host blake3 arm (native/pure-Python), whatever the backend
            if (opt.backend == "jax" or opt.digest_backend == "jax") and opt.digester == "sha256"
            else _HostDigester(opt.digester),
            asm,
        )
        pack = _Pack(opt, chunker, asm, queue, _pack_threads(), codec, budget, stats, begun)
        metas, opaque_dirs, plan, n_members = _scan(src_tar, raw, opt, queue, chunker)
        stages.annotate(
            members=n_members,
            files_planned=len(plan),
            bytes_planned=sum(size for _m, _o, size in plan),
        )
        declined: list = []
        while plan:
            lane = choose_lane(
                opt,
                in_memory=raw is not None,
                threads=pack.threads,
                host_fused=chunker.fused,
                has_dict=chunk_dict is not None,
                codec_active=codec is not None,
                seeded=bool(asm.own or asm.uoff or queue.pending or queue.in_flight),
                declined=declined,
            )
            try:
                # closing: an error in the walk ends the lane (its workers) now
                with closing(lane(pack, plan, arr, stages)) as results:
                    for (meta, off, size), (cuts, digests) in zip(plan, results, strict=True):
                        view = raw[off : off + size]
                        start = 0
                        batch = []
                        for cut in cuts:
                            cut = int(cut)
                            batch.append((meta, view[start:cut]))
                            start = cut
                        if digests is None:
                            for _meta, chunk in batch:
                                queue.add(meta, chunk)
                        elif batch:
                            asm.process(batch, digests)
                break
            except _LaneDeclined:
                declined.append(lane)
    finally:
        if begun is not None:
            # finished by _lane_device, or never (the scan raised, no file
            # planned, another lane): nothing waits for what was enqueued,
            # and it counted nowhere
            begun.close()
    if stages.running != "pack:dedup":
        stages.next("pack:dedup")
    queue.drain()
    stages.annotate(
        chunks=sum(len(m.chunks) for m in metas.values()),
        unique=len(asm.uncomp_offsets),
        dict_hits=len(asm.dict_hits),
    )
    stages.next("pack:compress_write", uncompressed_bytes=asm.uoff)
    section.finish()
    blob_size = section.coff
    stages.annotate(blob_bytes=blob_size)
    stages.next("pack:bootstrap")
    if blob_size:
        out.write(nydus_tar.make_header(toc.ENTRY_BLOB_DATA, blob_size))
    bootstrap, boot_bytes, toc_entries = emit_bootstrap(metas, opaque_dirs, asm, opt, out.tell())
    out.write(boot_bytes)
    out.write(nydus_tar.make_header(toc.ENTRY_BOOTSTRAP, len(boot_bytes)))
    toc_bytes = toc.pack_toc(toc_entries)
    out.write(toc_bytes)
    out.write(nydus_tar.make_header(toc.ENTRY_BLOB_TOC, len(toc_bytes)))
    stages.annotate(
        inodes=len(bootstrap.inodes),
        chunk_records=len(bootstrap.chunks),
        bootstrap_bytes=len(boot_bytes),
    )
    return PackResult(
        blob_id=bootstrap.blobs[0].blob_id if blob_size else "",
        blob_size=blob_size,
        bootstrap=boot_bytes,
        referenced_blob_ids=[b.blob_id for b in bootstrap.blobs],
    )


def emit_bootstrap(metas: dict, opaque_dirs: list, asm: _Assembler, opt: PackOption, boot_off: int):
    """-> (the layer's Bootstrap, its bytes, the blob's TOC entries), from
    the scan's ``metas`` and ``opaque_dirs`` (completed in place: missing
    parents, opaque marks) and the assembler's tables after
    ``section.finish()``; the bootstrap will lie at ``boot_off`` in the blob."""
    section, chunk_dict = asm.section, asm.chunk_dict
    blob_size = section.coff
    blob_id = section.hasher.hexdigest() if blob_size else ""
    # Synthesize root + missing parents (metadata only).
    for p in fstree.missing_parents(metas):
        metas[p] = _Meta(entry=fstree.FileEntry(path=p, mode=stat.S_IFDIR | 0o755))
    for d in opaque_dirs:
        if d not in metas:
            metas[d] = _Meta(entry=fstree.FileEntry(path=d, mode=stat.S_IFDIR | 0o755))
        metas[d].entry.flags |= fstree.INODE_FLAG_OPAQUE
        metas[d].entry.xattrs[fstree.OPAQUE_XATTR] = b"y"

    # Blob + cipher + batch tables (own blob first, then dict blobs).
    blob_table: list[BlobRecord] = []
    cipher_table: list[CipherRecord] = []
    batch_table: list[BatchRecord] = []
    blob_index_of: dict[str, int] = {}
    if blob_size:
        blob_index_of[blob_id] = 0
        blob_table.append(
            BlobRecord(
                blob_id=blob_id,
                compressed_size=blob_size,
                uncompressed_size=asm.uoff,
                chunk_count=len(asm.uncomp_offsets),
            )
        )
        cipher_table.append(section.cipher or CipherRecord())
        for coff_b, base_u, usize in section.batches:
            batch_table.append(BatchRecord(0, coff_b, base_u, usize))
    for bid in asm.dict_blobs_used:
        new_idx = len(blob_table)
        blob_index_of[bid] = new_idx
        dict_idx, dict_rec = next(
            (i, b) for i, b in enumerate(chunk_dict.bootstrap.blobs) if b.blob_id == bid
        )
        blob_table.append(
            BlobRecord(
                blob_id=bid,
                compressed_size=dict_rec.compressed_size,
                uncompressed_size=dict_rec.uncompressed_size,
                chunk_count=dict_rec.chunk_count,
                flags=dict_rec.flags,
            )
        )
        cipher_table.append(chunk_dict.bootstrap.cipher_for(dict_idx) or CipherRecord())
        for b in chunk_dict.bootstrap.batches:
            if b.blob_index == dict_idx:
                batch_table.append(
                    BatchRecord(new_idx, b.compressed_offset, b.uncompressed_base, b.uncompressed_size)
                )

    # Inodes + chunk table in path-sorted order (bootstrap serialization
    # order), records resolved against the final extent table.
    inodes: list[Inode] = []
    chunk_records: list[ChunkRecord] = []
    for path in sorted(metas):
        meta = metas[path]
        inode = fstree.entry_to_inode(meta.entry)
        inode.size = meta.size
        if meta.chunks:
            inode.chunk_index = len(chunk_records)
            inode.chunk_count = len(meta.chunks)
            for ref in meta.chunks:
                if ref.dict_hit is not None:
                    hit = ref.dict_hit
                    chunk_records.append(
                        ChunkRecord(
                            digest=ref.digest,
                            blob_index=blob_index_of[chunk_dict.blob_id_for(hit)],
                            flags=hit.flags,
                            uncompressed_offset=hit.uncompressed_offset,
                            compressed_offset=hit.compressed_offset,
                            uncompressed_size=hit.uncompressed_size,
                            compressed_size=hit.compressed_size,
                        )
                    )
                else:
                    coff_c, csize, cflag = section.extents[ref.uniq_idx]
                    chunk_records.append(
                        ChunkRecord(
                            digest=ref.digest,
                            blob_index=blob_index_of[blob_id],
                            flags=cflag,
                            uncompressed_offset=asm.uncomp_offsets[ref.uniq_idx],
                            compressed_offset=coff_c,
                            uncompressed_size=ref.size,
                            compressed_size=csize,
                        )
                    )
        inodes.append(inode)

    bootstrap = Bootstrap(
        version=opt.fs_version,
        chunk_size=opt.chunk_size,
        inodes=inodes,
        chunks=chunk_records,
        blobs=blob_table,
        ciphers=cipher_table if any(c.algo for c in cipher_table) else [],
        batches=batch_table,
        prefetch=match_prefetch_paths(inodes, opt.prefetch_patterns)
        if opt.prefetch_patterns
        else [],
    )
    boot_bytes = bootstrap.to_bytes()
    entries = [(toc.ENTRY_BOOTSTRAP, hashlib.sha256(boot_bytes).digest(), boot_off, len(boot_bytes))]
    if blob_size:
        entries.insert(0, (toc.ENTRY_BLOB_DATA, section.hasher.digest(), 0, blob_size))
    return bootstrap, boot_bytes, [
        toc.TOCEntry(
            name=name,
            flags=constants.COMPRESSOR_NONE,
            uncompressed_digest=digest,
            compressed_offset=offset,
            compressed_size=size,
            uncompressed_size=size,
        )
        for name, digest, offset, size in entries
    ]
