"""Pack / Merge / Unpack — the conversion hot path, TPU-backed.

Reference surface: ``Pack`` (convert_unix.go:325), ``Merge`` (:560),
``Unpack`` (:669). The external ``nydus-image`` process the reference shells
out to (tool/builder.go:148-362) is replaced by in-process stages:

- chunk + digest on device (ops/chunker.ChunkDigestEngine),
- chunk-dict dedup probe (models/bootstrap.ChunkDict host-side, or the
  sharded HBM table parallel/sharded_dict for batch conversion),
- bootstrap emission (models/bootstrap), blob framing (models/nydus_tar).

Output shape per layer (framed per models/nydus_tar):
``image.blob`` (per-chunk-compressed data) | ``image.boot`` (layer
bootstrap) | ``rafs.blob.toc``.
"""

from __future__ import annotations

import io
import stat
import tarfile
import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import BinaryIO, Callable, Optional

from nydus_snapshotter_tpu.utils.zstdcompat import zstandard

from nydus_snapshotter_tpu import constants, trace
from nydus_snapshotter_tpu.converter import crypto
from nydus_snapshotter_tpu.converter.types import ConvertError, MergeOption, PackOption, UnpackOption
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
from nydus_snapshotter_tpu.utils import lz4

_ZSTD_LEVEL = constants.ZSTD_LEVEL


@dataclass
class PackResult:
    blob_id: str  # hex sha256 of the image.blob section ("" if fully deduped)
    blob_size: int
    bootstrap: bytes
    referenced_blob_ids: list[str]


@dataclass
class MergeResult:
    bootstrap: bytes
    blob_digests: list[str]  # referenced blob ids after dedup, table order


def _make_compressor(compressor: str, lz4_accel: int = 1, codec=None):
    """One reusable codec per Pack — a fresh zstd context per chunk costs
    allocation/init for every one of the thousands of chunks in a layer.

    ``codec``: an :class:`~nydus_snapshotter_tpu.converter.codec.AdaptiveCodec`
    takes over the zstd lane (probe/bypass/per-class levels/trained
    dict); ``None`` is the byte-identical fixed-level default."""
    if codec is not None and compressor == "zstd":
        return codec.encode
    if compressor == "zstd":
        from nydus_snapshotter_tpu.utils import zstd as zstd_native

        if zstd_native.available():
            # System libzstd: byte-identical to the fused native section
            # assembly (which dlopens the same library) — the bundled
            # zstandard build can emit different frames (utils/zstd.py).
            return lambda data: (
                zstd_native.compress_block(data, _ZSTD_LEVEL),
                constants.COMPRESSOR_ZSTD,
            )
        ctx = zstandard.ZstdCompressor(level=_ZSTD_LEVEL)
        return lambda data: (ctx.compress(data), constants.COMPRESSOR_ZSTD)
    if compressor == "lz4_block":
        return lambda data: (
            lz4.compress_block(data, lz4_accel),
            constants.COMPRESSOR_LZ4_BLOCK,
        )
    return lambda data: (data, constants.COMPRESSOR_NONE)


class ThreadSafeCompressor:
    """Per-thread codec contexts for parallel speculative compression.

    ZstdCompressor instances are not safe for concurrent calls; output is
    still deterministic across contexts (same level, single-threaded
    contexts), so racing threads produce identical bytes.

    With an adaptive ``codec`` the call routes straight to
    ``codec.encode`` — the codec engine keeps its own per-worker pinned
    contexts and is deterministic in chunk content, so the same racing
    invariant holds.
    """

    def __init__(self, compressor: str, lz4_accel: int = 1, codec=None):
        import threading

        self._kind = compressor
        self._lz4_accel = lz4_accel
        self._codec = codec if (codec is not None and compressor == "zstd") else None
        self._tls = threading.local()

    def __call__(self, data):
        if self._codec is not None:
            return self._codec.encode(data)
        fn = getattr(self._tls, "fn", None)
        if fn is None:
            fn = _make_compressor(self._kind, self._lz4_accel)
            self._tls.fn = fn
        return fn(data)

    def encode_many(self, views, n_threads: int = 1):
        """Batch counterpart of ``__call__``: ``[(payload, flag)]``
        byte-identical to ``[self(v) for v in views]``.

        Routes the adaptive codec's :meth:`AdaptiveCodec.encode_batch`,
        or — on the plain system-libzstd lane — one GIL-released native
        batch call at the fixed level (``ntpu_encode_batch`` is one-shot
        ``ZSTD_compressCCtx`` like ``compress_block``, so frames match).
        Everything else (lz4, store-raw, the bundled-zstandard fallback)
        loops per chunk.
        """
        if self._codec is not None:
            return self._codec.encode_batch(views, n_threads=n_threads)
        if self._kind == "zstd" and views:
            from nydus_snapshotter_tpu.ops import native_cdc
            from nydus_snapshotter_tpu.utils import zstd as zstd_native

            if zstd_native.available() and native_cdc.encode_batch_available():
                buf, ext = native_cdc.concat_extents(views)
                res = native_cdc.encode_batch_native(buf, ext, _ZSTD_LEVEL, n_threads)
                if res is not None:
                    payloads, comp, _digests = res
                    return [
                        (
                            payloads[
                                int(comp[k, 0]) : int(comp[k, 0]) + int(comp[k, 1])
                            ].tobytes(),
                            constants.COMPRESSOR_ZSTD,
                        )
                        for k in range(len(views))
                    ]
        return [self(v) for v in views]


def _decompress_chunk(data: bytes, flags: int, expect_size: int) -> bytes:
    comp = flags & constants.COMPRESSOR_MASK
    if comp == constants.COMPRESSOR_ZSTD:
        from nydus_snapshotter_tpu.converter import codec as codec_mod
        from nydus_snapshotter_tpu.utils import zstdcompat

        if codec_mod.is_trained_frame(data):
            # Versioned trained-dict frame (nZD1 header): decodes only
            # with the dictionary it was trained with — a reader that
            # lacks it must fail loudly, never emit garbage bytes.
            try:
                return codec_mod.decode_trained_frame(data, expect_size)
            except codec_mod.CodecError as e:
                raise ConvertError(str(e)) from e
        try:
            # Pooled-DCtx decode path: no per-call context allocation
            # (the previous per-call ZstdDecompressor() construction was
            # measurable on the lazy-read hot path).
            return zstdcompat.decompress_block(
                data, max_output_size=max(expect_size, 1)
            )
        except Exception:
            # Any conforming frame decodes identically on the package
            # decompressor; keep it as the compatibility net.
            return zstandard.ZstdDecompressor().decompress(
                data, max_output_size=max(expect_size, 1)
            )
    if comp == constants.COMPRESSOR_LZ4_BLOCK:
        return lz4.decompress_block(data, expect_size)
    if comp == constants.COMPRESSOR_GZIP:
        # estargz chunks are whole gzip members left in place by the index
        # builder (stargz/index.py) — the lazy read path inflates them here.
        # The member carries tar padding (and possibly the next entry's
        # header member), so longer-than-expected output is normal and
        # truncated; SHORTER output means a corrupt blob.
        import gzip
        import zlib

        try:
            out = gzip.decompress(data)
        except (OSError, EOFError, zlib.error) as e:
            raise ConvertError(f"corrupt gzip chunk: {e}") from e
        if expect_size:
            if len(out) < expect_size:
                raise ConvertError(
                    f"gzip chunk inflated to {len(out)} bytes < expected {expect_size}"
                )
            return out[:expect_size]
        return out
    if comp in (constants.COMPRESSOR_NONE, 0):
        return data
    raise ConvertError(f"unsupported chunk compressor flags {flags:#x}")


class BlobReader:
    """Random-access chunk reads from one blob's data section.

    Centralizes the three storage transforms a chunk record can carry —
    per-chunk compression, batch packing (CHUNK_FLAG_BATCH: several small
    chunks share one compressed extent), and blob encryption (seekable
    AES-CTR, converter/crypto.py) — so Unpack and the lazy-read daemon
    resolve chunks through identical logic.

    ``read_at(offset, size)`` returns raw (still-encrypted) blob bytes.
    """

    # Decompressed batches kept hot per reader — bounded so a long-lived
    # daemon doesn't pin every batch it ever read.
    BATCH_CACHE_BYTES = 32 << 20

    def __init__(
        self,
        bootstrap: Bootstrap,
        blob_index: int,
        read_at: Callable[[int, int], bytes],
        batch_map: Optional[dict[tuple[int, int], tuple[int, int]]] = None,
        gzip_stream=None,
        zstd_stream=None,
    ):
        self.bootstrap = bootstrap
        self.blob_index = blob_index
        self.read_at = read_at
        self.cipher = bootstrap.cipher_for(blob_index)
        if self.cipher is not None and self.cipher.algo != crypto.CIPHER_AES_256_CTR:
            raise ConvertError(f"unsupported blob cipher algo {self.cipher.algo}")
        # (blob_index, compressed_offset) -> (uncompressed_base, size), from
        # the bootstrap's batch table. Callers constructing several readers
        # can share one batch_map to avoid rebuilding it per blob.
        self._batch_map = bootstrap.batch_map() if batch_map is None else batch_map
        # The daemon shares one reader per blob across request threads.
        self._batch_lock = threading.Lock()
        self._batch_cache: "OrderedDict[int, bytes]" = OrderedDict()
        self._batch_cache_bytes = 0
        # OCIRef blobs: a checkpointed cursor into the original gzip
        # stream. The default is the in-process GzipStreamReader (built
        # lazily, serialized by _gzip_lock — its inflate cursor is
        # stateful); a caller holding a persisted soci index injects a
        # SociStreamReader instead, whose `concurrent` flag skips the
        # lock (each read owns its own inflate state).
        self._gzip_stream = gzip_stream
        self._gzip_lock = threading.Lock()
        # Same arrangement for whole-zstd OCIRef blobs: frame-indexed
        # ZstdStreamReader (concurrent) injected by the daemon, or the
        # in-process sequential cursor built lazily under the lock.
        self._zstd_stream = zstd_stream
        self._zstd_lock = threading.Lock()

    def mount_gzip_stream(self, stream) -> None:
        """Swap in a checkpoint-indexed gzip reader (soci/blob.py) after
        construction: the daemon resolves the index store off its reader
        lock, so the stream arrives late. The attribute swap is atomic;
        reads served before it used the sequential path — identical
        bytes, just without checkpoint resume."""
        self._gzip_stream = stream

    def mount_zstd_stream(self, stream) -> None:
        """Swap in a frame-indexed zstd reader (soci/zblob.py) after
        construction — the zstd mirror of :meth:`mount_gzip_stream`,
        with identical atomicity and identical-bytes semantics."""
        self._zstd_stream = stream

    def _read_plain(self, offset: int, size: int) -> bytes:
        raw = self.read_at(offset, size)
        if len(raw) != size:
            raise ConvertError(
                f"blob {self.blob_index}: short read at {offset} "
                f"({len(raw)} of {size} bytes)"
            )
        if self.cipher is not None:
            raw = crypto.decrypt_range(raw, offset, self.cipher.key, self.cipher.iv)
        return raw

    def chunk_data(self, rec: ChunkRecord) -> bytes:
        """The uncompressed data of one chunk record."""
        if rec.blob_index != self.blob_index:
            raise ConvertError("chunk record belongs to a different blob")
        from nydus_snapshotter_tpu.converter.zran import (
            CHUNK_FLAG_GZIP_STREAM,
            GzipStreamReader,
        )

        if rec.flags & CHUNK_FLAG_GZIP_STREAM:
            # OCIRef: offsets address the decompressed stream of the
            # original .tar.gz blob (converter/zran.py).
            if getattr(self._gzip_stream, "concurrent", False):
                return self._gzip_stream.read_range(
                    rec.uncompressed_offset, rec.uncompressed_size
                )
            with self._gzip_lock:
                if self._gzip_stream is None:
                    self._gzip_stream = GzipStreamReader(
                        self._read_plain,
                        self.bootstrap.blobs[self.blob_index].compressed_size,
                    )
                return self._gzip_stream.read_range(
                    rec.uncompressed_offset, rec.uncompressed_size
                )
        from nydus_snapshotter_tpu.converter.zstd_ref import (
            CHUNK_FLAG_ZSTD_STREAM,
            ZstdSequentialReader,
        )

        if rec.flags & CHUNK_FLAG_ZSTD_STREAM:
            # OCIRef: offsets address the decompressed stream of the
            # original .tar.zst blob (converter/zstd_ref.py).
            if getattr(self._zstd_stream, "concurrent", False):
                return self._zstd_stream.read_range(
                    rec.uncompressed_offset, rec.uncompressed_size
                )
            with self._zstd_lock:
                if self._zstd_stream is None:
                    self._zstd_stream = ZstdSequentialReader(
                        self._read_plain,
                        self.bootstrap.blobs[self.blob_index].compressed_size,
                    )
                return self._zstd_stream.read_range(
                    rec.uncompressed_offset, rec.uncompressed_size
                )
        if rec.flags & CHUNK_FLAG_BATCH:
            extent = self._batch_map.get((self.blob_index, rec.compressed_offset))
            if extent is None:
                raise ConvertError(
                    f"batched chunk at blob {self.blob_index} offset "
                    f"{rec.compressed_offset} has no batch-table entry"
                )
            base, usize = extent
            with self._batch_lock:
                batch = self._batch_cache.get(rec.compressed_offset)
                if batch is not None:
                    self._batch_cache.move_to_end(rec.compressed_offset)
            if batch is None:
                raw = self._read_plain(rec.compressed_offset, rec.compressed_size)
                batch = _decompress_chunk(raw, rec.flags, usize)
                with self._batch_lock:
                    if rec.compressed_offset not in self._batch_cache:
                        self._batch_cache[rec.compressed_offset] = batch
                        self._batch_cache_bytes += len(batch)
                    while (
                        self._batch_cache_bytes > self.BATCH_CACHE_BYTES
                        and len(self._batch_cache) > 1
                    ):
                        _, evicted = self._batch_cache.popitem(last=False)
                        self._batch_cache_bytes -= len(evicted)
            inner = rec.uncompressed_offset - base
            if inner < 0 or inner + rec.uncompressed_size > len(batch):
                raise ConvertError("batch chunk slice overflows its batch")
            return batch[inner : inner + rec.uncompressed_size]
        raw = self._read_plain(rec.compressed_offset, rec.compressed_size)
        return _decompress_chunk(raw, rec.flags, rec.uncompressed_size)


def make_bytes_reader(
    bootstrap: Bootstrap, blob_index: int, blob: bytes, batch_map=None
) -> BlobReader:
    return BlobReader(
        bootstrap, blob_index, lambda off, size: blob[off : off + size], batch_map=batch_map
    )


# ---------------------------------------------------------------------------
# Pack
# ---------------------------------------------------------------------------


def Pack(
    dest: BinaryIO,
    src_tar: "BinaryIO | bytes | np.ndarray",
    opt: PackOption,
    chunk_dict=None,
    stats: dict | None = None,
    budget=None,
    codec=None,
) -> PackResult:
    """Convert one OCI layer tar into a nydus blob stream written to dest.

    Reference semantics (convert_unix.go:325-539): stream in an uncompressed
    layer tar, emit the tar-like nydus blob; chunk-dict hits are not stored,
    only referenced. Implementation: the bounded-memory streaming pipeline
    in converter/stream.py (tar stream -> incremental CDC -> batched
    digests -> dedup -> compress -> dest), shared by in-memory and
    streaming callers alike. On multi-worker hosts the per-layer stages
    overlap through the stage-parallel executor (parallel/pipeline.py);
    ``budget`` optionally pins that executor to a caller-owned
    MemoryBudget (batch conversion shares one across layers). ``codec``
    optionally pins an adaptive codec engine (converter/codec.py) for
    the zstd lane; ``None`` resolves from config/env (and stays the
    byte-identical fixed-level lane when the engine is off, the
    default).
    """
    from nydus_snapshotter_tpu import failpoint
    from nydus_snapshotter_tpu.converter.stream import pack_stream

    failpoint.hit("converter.pack")
    return pack_stream(
        dest,
        src_tar,
        opt,
        chunk_dict=chunk_dict,
        stats=stats,
        budget=budget,
        codec=codec,
    )


def pack_layer(
    src_tar: bytes,
    opt: PackOption,
    chunk_dict=None,
    stats: dict | None = None,
    budget=None,
    codec=None,
) -> tuple[bytes, PackResult]:
    """Convenience: Pack to bytes."""
    out = io.BytesIO()
    res = Pack(
        out, src_tar, opt, chunk_dict=chunk_dict, stats=stats, budget=budget,
        codec=codec,
    )
    return out.getvalue(), res


# ---------------------------------------------------------------------------
# Merge
# ---------------------------------------------------------------------------


@dataclass
class _Node:
    """Overlay node carrying an inode plus its chunks (blob ids resolved)."""

    inode: Inode
    chunks: list[tuple[ChunkRecord, str]] = field(default_factory=list)

    @property
    def path(self) -> str:
        return self.inode.path

    @property
    def is_dir(self) -> bool:
        return stat.S_ISDIR(self.inode.mode)

    @property
    def is_whiteout(self) -> bool:
        from nydus_snapshotter_tpu.models.bootstrap import INODE_FLAG_WHITEOUT

        return bool(self.inode.flags & INODE_FLAG_WHITEOUT)

    @property
    def flags(self) -> int:
        return self.inode.flags


def _layer_nodes(bootstrap: Bootstrap) -> list[_Node]:
    blob_ids = [b.blob_id for b in bootstrap.blobs]
    nodes = []
    for inode in bootstrap.inodes:
        chunks = [
            (c, blob_ids[c.blob_index])
            for c in bootstrap.chunks[inode.chunk_index : inode.chunk_index + inode.chunk_count]
        ]
        nodes.append(_Node(inode=inode, chunks=chunks))
    return nodes


def bootstrap_from_layer_blob(blob: bytes) -> Bootstrap:
    """Extract the layer bootstrap from a packed nydus blob stream. The
    embedded section may be in either layout — native, or the real
    toolchain's v5/v6 (a reference-built framed layer, convert_unix.go's
    packToTar shape) — and is auto-bridged."""
    from nydus_snapshotter_tpu.models.nydus_real import load_any_bootstrap

    f = io.BytesIO(blob)
    loc = nydus_tar.seek_file_by_tar_header(f, len(blob), toc.ENTRY_BOOTSTRAP)
    if loc is None:
        raise ConvertError("layer blob carries no bootstrap section")
    off, size = loc
    return load_any_bootstrap(blob[off : off + size])


def bootstrap_from_bootstrap_layer(data: bytes) -> Bootstrap:
    """Extract the image bootstrap from a (decompressed) bootstrap *layer*:
    a standard tar carrying ``image/image.boot``
    (constant.go BootstrapFileNameInLayer, written by packToTar)."""
    try:
        with tarfile.open(fileobj=io.BytesIO(data), mode="r:") as tf:
            for member in tf:
                if member.name in (layout.BOOTSTRAP_FILE, "./" + layout.BOOTSTRAP_FILE):
                    extracted = tf.extractfile(member)
                    if extracted is None:
                        break
                    return Bootstrap.from_bytes(extracted.read())
    except (tarfile.TarError, OSError) as e:
        raise ConvertError(f"bad bootstrap layer tar: {e}") from e
    raise ConvertError("bootstrap layer carries no image/image.boot")


def match_prefetch_paths(inodes, patterns: str) -> list[str]:
    """Resolve prefetch patterns to regular-file inode paths, hint order.

    Reference semantics (--prefetch-files, one path per line,
    daemon_adaptor.go:179-185): each line names a file or a directory
    prefix; directories expand to every regular file beneath them. Unknown
    patterns are skipped (hints, not requirements).
    """
    import stat as _stat

    wanted: list[str] = []
    seen: set[str] = set()
    lines = [ln.strip() for ln in patterns.splitlines() if ln.strip()]
    reg_paths = [i.path for i in inodes if _stat.S_ISREG(i.mode)]
    for line in lines:
        norm = "/" + line.strip("/") if line != "/" else "/"
        prefix = norm if norm == "/" else norm + "/"
        for path in reg_paths:
            if (path == norm or path.startswith(prefix)) and path not in seen:
                seen.add(path)
                wanted.append(path)
    return wanted


def Merge(
    layers: list[bytes | Bootstrap],
    opt: MergeOption,
    chunk_dict=None,
) -> MergeResult:
    """Merge per-layer bootstraps into one image bootstrap.

    ``layers`` are packed layer blobs (or already-parsed bootstraps), lowest
    first. Returns the image bootstrap plus the dedup result: the blob ids
    actually referenced (reference Merge surface convert_unix.go:560-666,
    whose blob-digest list comes from merge-output.json,
    tool/builder.go:278-294). ``chunk_dict`` passes an already-loaded dict
    object (batch conversion); ``opt.chunk_dict_path`` is the file fallback.

    Traced as consecutive leaf spans under a ``convert.merge`` root
    (docs/observability.md): ``merge:parse`` (dictionary, parent and each
    layer's bootstrap), ``merge:overlay`` (overlay, dedup re-pointing,
    tables), ``merge:emit`` (serialization).
    """
    with trace.batch_span("convert.merge"), trace.Stages() as stages:
        return _merge(layers, opt, chunk_dict, stages)


def _merge(layers, opt, chunk_dict, stages) -> MergeResult:
    if not layers:
        raise ConvertError("merge needs at least one layer")
    stages.next("merge:parse", layers=len(layers))
    if chunk_dict is None and opt.chunk_dict_path:
        from nydus_snapshotter_tpu.parallel.dict_service import open_chunk_dict

        chunk_dict = open_chunk_dict(opt.chunk_dict_path)
    from nydus_snapshotter_tpu.models.nydus_real import load_any_bootstrap

    parent: Optional[Bootstrap] = None
    if opt.parent_bootstrap_path:
        with open(opt.parent_bootstrap_path, "rb") as f:
            parent = load_any_bootstrap(f.read())

    def _layer_bootstrap(layer: bytes) -> Bootstrap:
        # A framed layer stream (pack output) or a bare bootstrap in
        # either layout — the reference Merge takes per-layer bootstraps
        # (convert_unix.go:560-607), including real-toolchain ones.
        try:
            return bootstrap_from_layer_blob(layer)
        except (ConvertError, nydus_tar.TarFramingError, ValueError) as frame_err:
            try:
                return load_any_bootstrap(layer)
            except Exception as boot_err:
                # keep the framing diagnosis AND the caller-visible type
                raise ConvertError(
                    f"layer is neither a framed blob ({frame_err}) nor a "
                    f"bootstrap ({boot_err})"
                ) from frame_err

    merged: dict[str, _Node] = {}
    boots: list[Bootstrap] = []
    if parent is not None:
        boots.append(parent)
    for layer in layers:
        boots.append(
            layer if isinstance(layer, Bootstrap) else _layer_bootstrap(layer)
        )
    stages.annotate(
        inodes=sum(len(b.inodes) for b in boots),
        chunks=sum(len(b.chunks) for b in boots),
    )
    stages.next("merge:overlay")
    chunk_size = boots[-1].chunk_size
    version = opt.fs_version or boots[-1].version
    lower: list[_Node] = []
    for b in boots:
        lower = fstree.apply_overlay(lower, _layer_nodes(b))  # type: ignore[arg-type]

    # Chunk-dict dedup at merge time: chunks whose digest is in the dict are
    # re-pointed at the dict blob.
    inodes: list[Inode] = []
    chunk_records: list[ChunkRecord] = []
    blob_index_of: dict[str, int] = {}
    blob_records: dict[str, BlobRecord] = {}
    blob_ciphers: dict[str, CipherRecord] = {}
    blob_batches: dict[tuple[str, int], tuple[int, int]] = {}
    source_boots = boots + ([chunk_dict.bootstrap] if chunk_dict is not None else [])
    for b in source_boots:
        for i, rec in enumerate(b.blobs):
            blob_records.setdefault(rec.blob_id, rec)
            cipher = b.cipher_for(i)
            if cipher is not None:
                blob_ciphers.setdefault(rec.blob_id, cipher)
        ids = [r.blob_id for r in b.blobs]
        for br in b.batches:
            if br.blob_index < len(ids):
                blob_batches.setdefault(
                    (ids[br.blob_index], br.compressed_offset),
                    (br.uncompressed_base, br.uncompressed_size),
                )

    def blob_index(bid: str) -> int:
        if bid not in blob_index_of:
            blob_index_of[bid] = len(blob_index_of)
        return blob_index_of[bid]

    for node in lower:  # already path-sorted by apply_overlay
        inode = node.inode
        inode.chunk_index = len(chunk_records)
        inode.chunk_count = len(node.chunks)
        for rec, bid in node.chunks:
            hit = chunk_dict.get(rec.digest) if chunk_dict is not None else None
            if hit is not None:
                chunk_records.append(
                    ChunkRecord(
                        digest=rec.digest,
                        blob_index=blob_index(chunk_dict.blob_id_for(hit)),
                        flags=hit.flags,
                        uncompressed_offset=hit.uncompressed_offset,
                        compressed_offset=hit.compressed_offset,
                        uncompressed_size=hit.uncompressed_size,
                        compressed_size=hit.compressed_size,
                    )
                )
            else:
                rec2 = ChunkRecord(**{**rec.__dict__})
                rec2.blob_index = blob_index(bid)
                chunk_records.append(rec2)
        inodes.append(inode)

    blob_table = []
    cipher_table = []
    for bid, _idx in sorted(blob_index_of.items(), key=lambda kv: kv[1]):
        base = blob_records.get(bid)
        if base is None:
            raise ConvertError(f"chunk references unknown blob {bid}")
        blob_table.append(base)
        cipher_table.append(blob_ciphers.get(bid) or CipherRecord())
    batch_table = sorted(
        (
            BatchRecord(blob_index_of[bid], coff, u_base, usize)
            for (bid, coff), (u_base, usize) in blob_batches.items()
            if bid in blob_index_of
        ),
        key=lambda b: (b.blob_index, b.compressed_offset),
    )

    bootstrap = Bootstrap(
        version=version,
        chunk_size=chunk_size,
        inodes=inodes,
        chunks=chunk_records,
        blobs=blob_table,
        ciphers=cipher_table if any(c.algo for c in cipher_table) else [],
        batches=batch_table,
        prefetch=match_prefetch_paths(inodes, opt.prefetch_patterns)
        if opt.prefetch_patterns
        else [],
    )
    stages.annotate(inodes=len(inodes), chunks=len(chunk_records))
    stages.next("merge:emit")
    if opt.bootstrap_format in ("rafs-v5", "rafs-v6"):
        # Emit the image bootstrap in the reference toolchain's own
        # layout so its ecosystem can mount what this framework built.
        if bootstrap.ciphers or bootstrap.batches:
            raise ConvertError(
                "encrypted/batched bootstraps have no real-layout "
                "representation; use bootstrap_format='native'"
            )
        from nydus_snapshotter_tpu.models.nydus_real_write import (
            real_from_bootstrap,
            write_real_v5,
            write_real_v6,
        )

        from nydus_snapshotter_tpu.models.nydus_real import RealBootstrapError

        try:
            real = real_from_bootstrap(bootstrap, digester=opt.digester)
            boot_bytes = (
                write_real_v5(real)
                if opt.bootstrap_format == "rafs-v5"
                else write_real_v6(real)
            )
        except RealBootstrapError as e:
            raise ConvertError(f"real-layout emit failed: {e}") from e
    elif opt.bootstrap_format in ("", "native"):
        boot_bytes = bootstrap.to_bytes()
    else:
        raise ConvertError(
            f"unknown bootstrap_format {opt.bootstrap_format!r} "
            "(native | rafs-v5 | rafs-v6)"
        )
    if opt.with_tar:
        # Standard forward tar carrying image/image.boot — the bootstrap
        # *layer* format every consumer expects (reference packToTar;
        # referrer fetch unpacks it with plain tar, unpack.go:20-56).
        out = io.BytesIO()
        with tarfile.open(fileobj=out, mode="w:", format=tarfile.GNU_FORMAT) as tf:
            info = tarfile.TarInfo(layout.BOOTSTRAP_FILE)
            info.size = len(boot_bytes)
            info.mode = 0o444
            tf.addfile(info, io.BytesIO(boot_bytes))
        boot_bytes = out.getvalue()
    stages.annotate(bootstrap_bytes=len(boot_bytes))
    return MergeResult(
        bootstrap=boot_bytes,
        blob_digests=[b.blob_id for b in blob_table],
    )


# ---------------------------------------------------------------------------
# Unpack
# ---------------------------------------------------------------------------


def Unpack(
    bootstrap: bytes | Bootstrap,
    blob_provider: Callable[[str], bytes] | dict[str, bytes],
    opt: UnpackOption | None = None,
) -> bytes:
    """Rebuild the OCI tar from a bootstrap plus its blobs.

    ``blob_provider`` maps blob id → *blob data section* bytes (for a packed
    layer stream, pass the bytes of its ``image.blob`` section, see
    ``blob_data_from_layer_blob``). Reference surface convert_unix.go:669-733.
    Accepts REAL nydus-toolchain bootstraps too (auto-detected and bridged
    via models/nydus_real.load_any_bootstrap).
    """
    if isinstance(bootstrap, Bootstrap):
        bs = bootstrap
    else:
        from nydus_snapshotter_tpu.models.nydus_real import load_any_bootstrap

        bs = load_any_bootstrap(bootstrap)
    provider = blob_provider.__getitem__ if isinstance(blob_provider, dict) else blob_provider
    readers: dict[int, BlobReader] = {}
    batch_map = bs.batch_map()

    def reader_for(blob_index: int) -> BlobReader:
        if blob_index not in readers:
            blob = provider(bs.blobs[blob_index].blob_id)
            readers[blob_index] = make_bytes_reader(bs, blob_index, blob, batch_map)
        return readers[blob_index]

    entries: list[fstree.FileEntry] = []
    for inode in bs.inodes:
        data = b""
        if stat.S_ISREG(inode.mode) and inode.chunk_count and not inode.hardlink_target:
            parts = []
            for rec in bs.chunks[inode.chunk_index : inode.chunk_index + inode.chunk_count]:
                parts.append(reader_for(rec.blob_index).chunk_data(rec))
            data = b"".join(parts)
            if len(data) != inode.size:
                raise ConvertError(
                    f"unpacked {inode.path}: got {len(data)} bytes, inode says {inode.size}"
                )
        entries.append(fstree.inode_to_entry(inode, data))
    return fstree.tar_from_tree(entries)


def frame_bootstrap_only(boot_bytes: bytes) -> bytes:
    """Frame a metadata-only layer stream (image.boot + TOC, no data
    section) — the OCIRef/zran layer shape, consumable by Merge like any
    packed layer."""
    import hashlib as _hashlib

    toc_bytes = toc.pack_toc(
        [
            toc.TOCEntry(
                name=toc.ENTRY_BOOTSTRAP,
                flags=constants.COMPRESSOR_NONE,
                uncompressed_digest=_hashlib.sha256(boot_bytes).digest(),
                compressed_offset=0,
                compressed_size=len(boot_bytes),
                uncompressed_size=len(boot_bytes),
            )
        ]
    )
    return nydus_tar.pack_entries(
        [(toc.ENTRY_BOOTSTRAP, boot_bytes), (toc.ENTRY_BLOB_TOC, toc_bytes)]
    )


def blob_data_from_layer_blob(blob: bytes) -> bytes:
    """Extract the image.blob section from a packed layer stream ('' if none)."""
    f = io.BytesIO(blob)
    loc = nydus_tar.seek_file_by_tar_header(f, len(blob), toc.ENTRY_BLOB_DATA)
    if loc is None:
        return b""
    off, size = loc
    return blob[off : off + size]
