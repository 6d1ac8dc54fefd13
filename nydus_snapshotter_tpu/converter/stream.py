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
"""

from __future__ import annotations

import hashlib
import math
import os
import stat
import tarfile
from dataclasses import dataclass, field
from typing import BinaryIO, Optional

import numpy as np

from nydus_snapshotter_tpu import constants, trace
from nydus_snapshotter_tpu.converter import crypto
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
from nydus_snapshotter_tpu.ops import cdc

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
        from nydus_snapshotter_tpu.ops.chunker import ChunkDigestEngine

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
        self.lookahead = (
            self._engine.params.max_size if self._engine.params else opt.chunk_size
        )
        # Fused single-pass chunk+digest (native SIMD bitmaps + SHA-NI):
        # when the engine's fused arm is available, each drain yields
        # (chunk, digest) pairs directly — no separate digest sweep, no
        # per-chunk batching copies. Digests of carried-over chunks are
        # recomputed next drain (a few % of bytes at the drain cadence).
        self.fused = self._engine._fused_available()
        self._buf = bytearray()

    def _boundaries(self, data: "bytes | bytearray | np.ndarray") -> np.ndarray:
        return self._engine.boundaries(data)

    def feed(self, seg: bytes) -> list[tuple[bytes, Optional[bytes]]]:
        self._buf += seg
        if len(self._buf) < 2 * self.lookahead:
            return []
        return self._drain(final=False)

    def finish(self) -> list[tuple[bytes, Optional[bytes]]]:
        out = self._drain(final=True)
        self._buf = bytearray()
        return out

    def _drain(self, final: bool) -> list[tuple[bytes, Optional[bytes]]]:
        buf = self._buf
        if not buf:
            return []
        # The engine converts bytes/bytearray via a shared-memory
        # frombuffer view — no copy; boundaries (and fused digests) are
        # computed before any mutation of the buffer.
        if self.fused:
            from nydus_snapshotter_tpu.ops import native_cdc

            cuts, digests = native_cdc.chunk_digest_native(
                buf, self._engine.params, digester=self._engine.digester
            )
        else:
            cuts, digests = self._boundaries(buf), None
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

    def chunk_whole(
        self, view: memoryview
    ) -> list[tuple[memoryview, Optional[bytes]]]:
        """Single-pass chunk(+digest) of a complete in-memory file.

        The in-memory fast path: no bytearray accumulation, no per-chunk
        bytes() materialization — chunks are zero-copy views into the
        caller's tar buffer (the reference avoids these copies by piping
        the raw stream straight into the builder process,
        pkg/converter/convert_unix.go:443-539).
        """
        if len(view) == 0:
            return []
        arr = np.frombuffer(view, dtype=np.uint8)
        if self.fused:
            from nydus_snapshotter_tpu.ops import native_cdc

            cuts, digests = native_cdc.chunk_digest_native(
                arr, self._engine.params, digester=self._engine.digester
            )
        else:
            cuts, digests = self._boundaries(arr), None
        out: list[tuple[memoryview, Optional[bytes]]] = []
        s = 0
        for i, c in enumerate(cuts):
            c = int(c)
            out.append(
                (
                    view[s:c],
                    digests[32 * i : 32 * (i + 1)] if digests is not None else None,
                )
            )
            s = c
        return out


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
        from nydus_snapshotter_tpu.ops.chunker import host_digests_for

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
        from nydus_snapshotter_tpu.ops import sha256

        self._max_blocks = sha256.n_padded_blocks(max_chunk)

    def submit(self, datas: list[bytes]):
        import jax.numpy as jnp

        from nydus_snapshotter_tpu.ops import sha256
        from nydus_snapshotter_tpu.ops.chunker import _pow2_ceil

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

        from nydus_snapshotter_tpu.ops import sha256

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
        from nydus_snapshotter_tpu.ops import native_cdc

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
        blob, comp_ext, digest = res
        self._adopt(blob, comp_ext, digest)

    def _adopt(self, blob, comp_extents, digest: bytes) -> None:
        """Adopt a native pass's assembled section (shared by finish()
        and finish_fused())."""
        self.extents = [
            (int(comp_extents[j, 0]), int(comp_extents[j, 1]), self._cflag)
            for j in range(comp_extents.shape[0])
        ]
        self.hasher._d = digest
        if blob.size:
            self.out.write(memoryview(blob))
        self.coff = int(blob.size)

    def finish_fused(self, blob, comp_extents, digest: bytes) -> None:
        """Adopt the whole-layer fused pass's output (ntpu_pack_files):
        the native call already compressed/assembled/hashed; nothing was
        ever add()ed, so the regular finish() stays a no-op."""
        self._adopt(blob, comp_extents, digest)


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
    ``bytes`` / ``bytearray`` / a 1-D uint8 array (for the fused backend
    best the head of a ``fused_convert.zeroed_buffer`` of ``padded_length``
    bytes, which the lane then uploads without a copy).

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
    ``pack:dedup``, ``pack:compress_write``, ``pack:bootstrap``.

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


def _pack_stream(dest, src_tar, opt, chunk_dict, stats, budget, codec, stages):
    """pack_stream's body; ``stages`` (trace.Stages) runs its leaf spans."""
    import io

    opt.validate()
    # In-memory layers take the zero-copy path: random-access tar parse,
    # whole-file views sliced straight out of the caller's buffer (the
    # bounded-memory streaming discipline below only matters for file-like
    # sources that may not fit in RAM).
    # A uint8 array counts too (cmd_pack reads a layer into one with the
    # fused lane's padding behind it, and the lane uploads that as it is).
    raw: Optional[memoryview] = None
    if isinstance(src_tar, (bytes, bytearray, np.ndarray)):
        if isinstance(src_tar, np.ndarray) and not (
            src_tar.dtype == np.uint8 and src_tar.ndim == 1 and src_tar.flags.c_contiguous
        ):
            raise ConvertError("an in-memory layer tar array must be contiguous 1-D uint8")
        raw = memoryview(src_tar)

    if chunk_dict is None and opt.chunk_dict_path:
        # service://<uds>[#namespace] connects a shared-dict mirror; any
        # other shape is the file-based dict as before.
        from nydus_snapshotter_tpu.parallel.dict_service import open_chunk_dict

        stages.next("pack:dict_load")
        chunk_dict = open_chunk_dict(opt.chunk_dict_path)
        stages.annotate(
            dict_chunks=len(chunk_dict), dict_blobs=len(chunk_dict.blob_ids())
        )
    # everything up to the chunk stage's own call is the scan: set-up, the
    # member walk, the plan's extents
    stages.next("pack:scan")
    from nydus_snapshotter_tpu.converter.convert import _make_compressor

    if codec is None:
        from nydus_snapshotter_tpu.converter import codec as codec_mod

        codec = codec_mod.resolve_codec(opt)

    out = _CountingWriter(dest)
    from nydus_snapshotter_tpu.ops import native_cdc

    compress = _make_compressor(opt.compressor, opt.lz4_acceleration, codec=codec)
    align_needed = opt.aligned_chunk and opt.fs_version == layout.RAFS_V5
    if (
        raw is not None
        and opt.compressor in ("none", "lz4_block", "zstd")
        # the adaptive codec owns per-chunk frame decisions — the native
        # section arms compress at one fixed level and would bypass it
        and codec is None
        and not opt.encrypt
        and not opt.batch_size
        and not align_needed
        and native_cdc.pack_section_available()
    ):
        section: "object" = _DeferredSectionWriter(out, opt, compress, raw)
    else:
        section = _SectionWriter(out, opt, compress)
    max_chunk = cdc.CDCParams(opt.chunk_size).max_size if opt.chunking == "cdc" else opt.chunk_size
    digester = (
        _DeviceDigester(max_chunk)
        # the device batch kernel is SHA-256; blake3 always digests on the
        # host blake3 arm (native/pure-Python), whatever the backend
        if (opt.backend == "jax" or opt.digest_backend == "jax")
        and opt.digester == "sha256"
        else _HostDigester(opt.digester)
    )

    metas: dict[str, _Meta] = {}
    opaque_dirs: list[str] = []

    # Dedup state (chunk order = tar order; deterministic).
    own_chunks: dict[bytes, int] = {}
    uncomp_offsets: list[int] = []
    uoff = 0
    dict_hits: dict[bytes, ChunkRecord] = {}
    dict_blobs_used: list[str] = []

    # One digest batch in flight: (handle, [(meta, data)]) pairs.
    pending: list[tuple[_Meta, bytes]] = []
    pending_bytes = 0
    in_flight: Optional[tuple[object, list[tuple[_Meta, bytes]]]] = None

    def _process(
        batch: list[tuple[_Meta, bytes]],
        digests: list[bytes],
        comp_cache: "Optional[dict[bytes, tuple[bytes, int]]]" = None,
    ) -> None:
        nonlocal uoff
        for (meta, data), digest in zip(batch, digests):
            ref = _ChunkRef(digest=digest, size=len(data))
            if chunk_dict is not None and digest not in dict_hits and digest not in own_chunks:
                hit = chunk_dict.get(digest)
                if hit is not None:
                    dict_hits[digest] = hit
                    bid = chunk_dict.blob_id_for(hit)
                    if bid not in dict_blobs_used:
                        dict_blobs_used.append(bid)
            if digest in dict_hits:
                ref.dict_hit = dict_hits[digest]
            else:
                idx = own_chunks.get(digest)
                if idx is None:
                    idx = len(uncomp_offsets)
                    own_chunks[digest] = idx
                    uncomp_offsets.append(uoff)
                    section.add(
                        idx,
                        data,
                        uoff,
                        # pop: each unique digest reaches here exactly once;
                        # releasing the entry keeps peak RSS at one chunk,
                        # not the whole compressed blob.
                        precomp=comp_cache.pop(digest, None) if comp_cache else None,
                    )
                    uoff += len(data)
                ref.uniq_idx = idx
            meta.chunks.append(ref)

    def _dispatch() -> None:
        nonlocal pending, pending_bytes, in_flight
        if in_flight is not None:
            handle, batch = in_flight
            _process(batch, digester.collect(handle))
            in_flight = None
        if pending:
            in_flight = (digester.submit([d for _, d in pending]), pending)
            pending = []
            pending_bytes = 0

    def _drain_all() -> None:
        _dispatch()  # collects old, dispatches remainder
        _dispatch()  # collects remainder

    def _add_chunk(meta: _Meta, data: bytes, digest: Optional[bytes] = None) -> None:
        nonlocal pending_bytes
        if digest is not None:
            # the fused chunker already digested this chunk (cache-warm,
            # single native pass); dedup/write it immediately, in order
            _process([(meta, data)], [digest])
            return
        pending.append((meta, data))
        pending_bytes += len(data)
        if pending_bytes >= DIGEST_BATCH_BYTES:
            _dispatch()

    shared_chunker = IncrementalChunker(opt)
    # In-memory plan: chunk/digest work is deferred during the header walk
    # so thousands of small files (≤ one chunk each — the node_modules
    # shape) batch into a single native SHA sweep over the tar buffer
    # instead of one engine call per file. Entries stay in tar order, so
    # the blob layout and dedup state are identical to immediate
    # processing. ("small", meta, off, size) | ("file", meta, off, size)
    plan: list[tuple[str, _Meta, int, int]] = []
    params = shared_chunker._engine.params
    small_max = params.min_size if params is not None else opt.chunk_size
    defer_small = raw is not None and shared_chunker.fused

    def _walk_member(info, data_off, tf) -> None:
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
            tag = "small" if defer_small and info.size <= small_max else "file"
            plan.append((tag, meta, data_off, info.size))
            return
        f = tf.extractfile(info)
        if f is None:
            raise ConvertError(f"tar member {path!r} has no data stream")
        chunker = IncrementalChunker(opt, engine=shared_chunker._engine)
        while True:
            seg = f.read(SEGMENT_BYTES)
            if not seg:
                break
            for chunk, digest in chunker.feed(seg):
                _add_chunk(meta, chunk, digest)
        for chunk, digest in chunker.finish():
            _add_chunk(meta, chunk, digest)

    n_members = 0
    members = _fast_tar_members(raw) if raw is not None else None
    if members is not None:
        n_members = len(members)
        for info, data_off in members:
            _walk_member(info, data_off, None)  # tf unused: data via raw
    else:
        try:
            # Random access for in-memory layers (tarfile's stream mode
            # copies every data byte through its internal block buffers).
            # io.BytesIO shares a bytes object and copies anything else,
            # so it is built only here, where the fast walk gave up.
            tf = tarfile.open(
                fileobj=io.BytesIO(src_tar) if raw is not None else src_tar,
                mode="r:" if raw is not None else "r|",
            )
        except tarfile.TarError as e:
            raise ConvertError(f"bad layer tar: {e}") from e
        with tf:
            try:
                for info in tf:
                    n_members += 1
                    _walk_member(
                        info,
                        info.offset_data if raw is not None else None,
                        tf,
                    )
            except tarfile.TarError as e:
                raise ConvertError(f"bad layer tar: {e}") from e
    stages.annotate(
        members=n_members,
        files_planned=len(plan),
        bytes_planned=sum(size for _t, _m, _o, size in plan),
    )
    if plan:
        from nydus_snapshotter_tpu.ops import native_cdc

        # the caller's own array where it gave one: the fused lane looks
        # for room behind it (fused_convert.lane_buffer)
        arr_all = (
            src_tar if isinstance(src_tar, np.ndarray) else np.frombuffer(raw, dtype=np.uint8)
        )
        n_threads = _pack_threads()
        # Single-thread fast lane: ONE native call fuses chunk+digest for
        # EVERY planned file (small and large alike — a <= min_size file
        # is exactly one CDC chunk, so the unified pass subsumes the
        # batched small-file digest sweep). Cut points, digests, dedup
        # and blob bytes are bit-identical to the per-file path.
        use_multi = (
            n_threads == 1
            and shared_chunker.fused
            and params is not None
            and opt.chunking == "cdc"
            and native_cdc.chunk_digest_multi_available()
        )
        # Whole-layer fused lane: chunk + digest + first-wins dedup +
        # compress + assemble + blob hash in ONE native call (the
        # reference's entire `nydus-image create` hot loop). Applies when
        # there is no chunk dict (dict probes stay in the Python dedup
        # lane) and the storage layout is the deferred writer's.
        if (
            use_multi
            and chunk_dict is None
            and isinstance(section, _DeferredSectionWriter)
            and native_cdc.pack_files_available()
            # the walk must not have seeded any chunk state already
            # (sparse members stream through _process during the walk):
            # the fused pass owns the WHOLE dedup/storage state or none.
            and uoff == 0
            and not own_chunks
            and not pending
            and in_flight is None
            and not section._items
        ):
            ext = np.asarray(
                [(off, size) for _t, _m, off, size in plan], dtype=np.int64
            )
            stages.next("pack:fused_pack")
            fused = native_cdc.pack_files(
                arr_all, ext, params, section._kind, section._accel, n_threads,
                digester=opt.digester,
            )
            if fused is not None:
                digs = fused["digests"]
                sizes_arr = fused["chunk_sizes"]
                uniq_arr = fused["chunk_uniq"]
                pos = 0
                for (_tag, meta, _off, _size), nc in zip(
                    plan, fused["file_nchunks"]
                ):
                    for k in range(int(nc)):
                        meta.chunks.append(
                            _ChunkRef(
                                digest=digs[32 * (pos + k) : 32 * (pos + k + 1)],
                                size=int(sizes_arr[pos + k]),
                                uniq_idx=int(uniq_arr[pos + k]),
                            )
                        )
                    pos += int(nc)
                usz = fused["uniq_sizes"]
                if len(usz):
                    uncomp_offsets = (
                        np.concatenate([[0], np.cumsum(usz[:-1])])
                        .astype(np.int64)
                        .tolist()
                    )
                    uoff = int(usz.sum())
                section.finish_fused(
                    fused["blob"], fused["comp_extents"], fused["blob_digest"]
                )
                plan = []
        if use_multi and plan:
            ext = np.asarray(
                [(off, size) for _t, _m, off, size in plan], dtype=np.int64
            )
            stages.next("pack:chunk_digest")
            ncuts_arr, cuts_all, digs_all = native_cdc.chunk_digest_multi(
                arr_all, ext, params, digester=opt.digester
            )
            stages.next("pack:dedup")
            pos = 0
            for (tag, meta, off, size), nc in zip(plan, ncuts_arr):
                nc = int(nc)
                view = raw[off : off + size]
                s = 0
                batch = []
                dlist = []
                for k in range(nc):
                    c = int(cuts_all[pos + k])
                    batch.append((meta, view[s:c]))
                    dlist.append(digs_all[32 * (pos + k) : 32 * (pos + k + 1)])
                    s = c
                _process(batch, dlist)
                pos += nc
            plan = []  # consumed; skip the per-file paths below
        # Device full-path lane (opt.backend == "fused"): the WHOLE layer's
        # files as one two-dispatch device batch (ops/fused_convert —
        # gear+compaction, then gather+digest), host keeping only cut
        # metadata. Dedup (incl. chunk-dict probes) and compression stay
        # in the _process lane, byte-identical to the host paths.
        if (
            plan
            and opt.backend == "fused"
            and params is not None
            and opt.chunking == "cdc"
        ):
            from nydus_snapshotter_tpu.ops import fused_convert

            feng = fused_convert.FusedDeviceEngine(
                chunk_size=opt.chunk_size, digester=opt.digester
            )
            stages.close()  # the lane runs its own stages: pack:lane.*
            try:
                # the tar is the lane's buffer, the plan's extents its table
                fres = feng.process_many(
                    fused_convert.Extents(
                        arr_all, [(off, size) for _t, _m, off, size in plan]
                    )
                )
            except fused_convert.FusedOverflow:
                fres = None  # pathological input: per-file paths below
                fused_convert.record_host_fallback()
            if fres is not None:
                stages.next("pack:dedup")
                stages.seconds.update(fres.span_seconds or {})  # pack:lane.*, once a pack
                for (_tag, meta, off, size), fcuts, dlist in zip(
                    plan, fres.cuts, fres.digests
                ):
                    view = raw[off : off + size]
                    s = 0
                    batch = []
                    for c in fcuts:
                        batch.append((meta, view[s : int(c)]))
                        s = int(c)
                    if batch:
                        _process(batch, dlist)
                plan = []
        small_items = [
            (arr_all, off, size) for tag, _m, off, size in plan if tag == "small"
        ]
        if plan:
            # the per-file lanes: chunking (here, or on the pipeline's
            # workers) interleaves with the ordered dedup walk file by
            # file, so the loop is ONE span, never one a file
            stages.next("pack:chunk_digest")
        if small_items:
            from nydus_snapshotter_tpu.ops.chunker import host_digests_for

            small_digests = iter(host_digests_for(opt.digester)(small_items))

        # Within-layer parallelism for multi-core hosts (the reference gets
        # it from the builder's internal thread pool): the stage-parallel
        # pipeline (parallel/pipeline.py) chunks + digests files on a
        # worker pool, speculatively compresses each unique chunk as soon
        # as its digest exists — compression is deterministic, so racing
        # duplicate digests write identical bytes — and the ordered serial
        # walk below only dedups + assembles. Queues between stages are
        # byte-bounded and compressed bytes in flight draw from a
        # MemoryBudget (shared across layers in batch conversion), so
        # convert memory stays independent of layer size and count. Blob
        # bytes are identical to the serial path (pinned by
        # tests/test_fast_tar.py and tests/test_pipeline_determinism.py).
        comp_cache: dict[bytes, tuple[bytes, int]] = {}  # serial-path default
        file_idxs = [i for i, (tag, *_rest) in enumerate(plan) if tag == "file"]
        # Host arms only: fused/native/numpy chunking is safe to call from
        # worker threads (GIL-dropping where it matters); the jax lanes
        # keep their own double-buffered device dispatch discipline.
        pipe = None
        if (
            n_threads > 1
            and len(file_idxs) > 1
            and opt.backend in ("hybrid", "numpy")
            and opt.digest_backend != "jax"
        ):
            from nydus_snapshotter_tpu.parallel import pipeline as pipeline_mod

            pcfg = pipeline_mod.resolve_config(n_threads)
            if pcfg.enabled:
                digest_fn = None
                if not shared_chunker.fused:
                    # Non-fused engines cut without digesting; digest in
                    # the worker (same bytes → same digests as the batched
                    # host dispatch) so dedup and speculative compression
                    # can run ahead of the ordered walk.
                    from nydus_snapshotter_tpu.ops.chunker import (
                        host_digests_for as _hdf,
                    )

                    digest_fn = _hdf(opt.digester)

                def _chunk_one(i: int):
                    _tag, _meta, off, size = plan[i]
                    chunks = shared_chunker.chunk_whole(raw[off : off + size])
                    if digest_fn is not None and chunks:
                        items = []
                        s = off
                        for view, _d in chunks:
                            items.append((arr_all, s, len(view)))
                            s += len(view)
                        digs = digest_fn(items)
                        chunks = [(v, d) for (v, _), d in zip(chunks, digs)]
                    return chunks

                compress_fn = None
                compress_eligible = None
                if opt.compressor in ("lz4_block", "zstd") and not isinstance(
                    section, _DeferredSectionWriter
                ):
                    # (Deferred sections compress inside the native pass
                    # with their own thread fan-out — speculating here
                    # would do the work twice.) Per-thread codec contexts:
                    # lz4 calls are stateless, zstd contexts are not
                    # thread-safe; both codecs are deterministic.
                    from nydus_snapshotter_tpu.converter.convert import (
                        ThreadSafeCompressor,
                    )

                    # ThreadSafeCompressor also carries the encode_many
                    # batch seam: pipeline compress workers drain up to
                    # [compression] batch_chunks queued chunks into one
                    # GIL-released native batch-encode call (byte-identical
                    # frames either way).
                    compress_fn = ThreadSafeCompressor(
                        opt.compressor, opt.lz4_acceleration, codec=codec
                    )
                    batch_limit = opt.batch_size

                    def compress_eligible(digest, view):
                        if batch_limit and len(view) < batch_limit:
                            return False  # batch-packed: compressed jointly
                        if chunk_dict is not None and chunk_dict.get(digest):
                            return False  # dict hit: never stored
                        return True

                pipe = pipeline_mod.ConvertPipeline(
                    items=[(i, plan[i][3]) for i in file_idxs],
                    chunk_fn=_chunk_one,
                    compress_fn=compress_fn,
                    compress_eligible=compress_eligible,
                    config=pcfg,
                    budget=budget,
                    stats=stats,
                )
                # Serial-path equivalence: any walk-time chunks (sparse
                # members) sit in the pending digest batches and would be
                # section.add'ed before the plan's chunks — drain them now
                # so the pipelined immediate _process keeps that order.
                _drain_all()

        from contextlib import nullcontext

        with pipe if pipe is not None else nullcontext():
            for i, (tag, meta, off, size) in enumerate(plan):
                view = raw[off : off + size]
                if tag == "small":  # ≤ min_size ⇒ exactly one chunk
                    _process([(meta, view)], [next(small_digests)])
                    continue
                chunks = (
                    pipe.chunks_for(i)
                    if pipe is not None
                    else shared_chunker.chunk_whole(view)
                )
                if chunks and chunks[0][1] is not None:
                    _process(
                        [(meta, c) for c, _ in chunks],
                        [d for _, d in chunks],
                        comp_cache=pipe.comp
                        if pipe is not None and pipe.compress_fn is not None
                        else comp_cache,
                    )
                else:
                    for chunk, digest in chunks:
                        _add_chunk(meta, chunk, digest)
    if stages.running != "pack:dedup":
        stages.next("pack:dedup")
    _drain_all()
    stages.annotate(
        chunks=sum(len(m.chunks) for m in metas.values()),
        unique=len(uncomp_offsets),
        dict_hits=len(dict_hits),
    )
    stages.next("pack:compress_write", uncompressed_bytes=uoff)
    section.finish()
    blob_size = section.coff
    stages.annotate(blob_bytes=blob_size)
    stages.next("pack:bootstrap")

    blob_id = section.hasher.hexdigest() if blob_size else ""
    if blob_size:
        out.write(nydus_tar.make_header(toc.ENTRY_BLOB_DATA, blob_size))

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
                uncompressed_size=uoff,
                chunk_count=len(uncomp_offsets),
            )
        )
        cipher_table.append(section.cipher or CipherRecord())
        for coff_b, base_u, usize in section.batches:
            batch_table.append(BatchRecord(0, coff_b, base_u, usize))
    for bid in dict_blobs_used:
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
                            uncompressed_offset=uncomp_offsets[ref.uniq_idx],
                            compressed_offset=coff_c,
                            uncompressed_size=ref.size,
                            compressed_size=csize,
                        )
                    )
        inodes.append(inode)

    from nydus_snapshotter_tpu.converter.convert import match_prefetch_paths

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

    toc_entries = []
    if blob_size:
        toc_entries.append(
            toc.TOCEntry(
                name=toc.ENTRY_BLOB_DATA,
                flags=constants.COMPRESSOR_NONE,
                uncompressed_digest=section.hasher.digest(),
                compressed_offset=0,
                compressed_size=blob_size,
                uncompressed_size=blob_size,
            )
        )
    boot_off = out.tell()
    out.write(boot_bytes)
    out.write(nydus_tar.make_header(toc.ENTRY_BOOTSTRAP, len(boot_bytes)))
    toc_entries.append(
        toc.TOCEntry(
            name=toc.ENTRY_BOOTSTRAP,
            flags=constants.COMPRESSOR_NONE,
            uncompressed_digest=hashlib.sha256(boot_bytes).digest(),
            compressed_offset=boot_off,
            compressed_size=len(boot_bytes),
            uncompressed_size=len(boot_bytes),
        )
    )
    toc_bytes = toc.pack_toc(toc_entries)
    out.write(toc_bytes)
    out.write(nydus_tar.make_header(toc.ENTRY_BLOB_TOC, len(toc_bytes)))
    stages.annotate(
        inodes=len(inodes),
        chunk_records=len(chunk_records),
        bootstrap_bytes=len(boot_bytes),
    )

    from nydus_snapshotter_tpu.converter.convert import PackResult

    return PackResult(
        blob_id=blob_id,
        blob_size=blob_size,
        bootstrap=boot_bytes,
        referenced_blob_ids=[b.blob_id for b in blob_table],
    )
