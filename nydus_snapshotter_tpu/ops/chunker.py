"""ChunkDigestEngine: windowed hash → cut resolution → batched digests.

This is the data-plane replacement for the reference's ``nydus-image create``
hot loop (chunking + digesting inside the Rust builder,
pkg/converter/tool/builder.go:148-178), decomposed TPU-first:

1. **Hash (device, parallel).** The stream is viewed as fixed-size windows
   (static shapes ⇒ one XLA compilation per window geometry). Each window
   batch is hashed position-parallel (ops/gear.py) and judged against both
   FastCDC masks; the kernel returns *packed candidate bitmaps*
   (uint32[N/32] per mask) so device→host traffic is N/4 bits per byte, not
   4 bytes per byte of hashes. A 31-byte tail carries the rolling window
   across seams, making windowed output bit-identical to whole-stream
   hashing.
2. **Cut resolution (host, over sparse candidates).** ops/cdc.py resolves
   min/normal/max rules per file in O(chunks · log candidates).
3. **Digest (device, vmapped).** Chunks are bucketed by padded block count
   (powers of two ⇒ few compiled shapes, bounded padding waste) and
   SHA-256'd as uint32 lanes (ops/sha256.py).

Fixed-size mode (nydus default) skips phase 1/2 and goes straight to
digesting.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from nydus_snapshotter_tpu.ops import cdc, gear, sha256

DEFAULT_WINDOW = 1 << 22  # 4 MiB per device window


def _pow2_ceil(n: int) -> int:
    return 1 << (n - 1).bit_length() if n > 1 else 1


@dataclass(frozen=True)
class ChunkMeta:
    offset: int
    size: int
    digest: bytes  # raw sha256 of the chunk data


@functools.partial(jax.jit, static_argnames=("n",))
def _hash_bitmaps_kernel(x: jax.Array, mask_s: jax.Array, mask_l: jax.Array, n: int):
    """Batch of windows → packed candidate bitmaps.

    x: uint8[B, n + GEAR_WINDOW - 1] (window prefixed by its 31-byte tail)
    returns (uint32[B, n//32], uint32[B, n//32]) for the two masks.

    Gather-free: the gear table value of every byte is computed elementwise
    (gear.mix32_jnp — TPU VPUs have no per-lane table lookup; the measured
    gathered variant ran at 0.1 GiB/s on a v5e chip) and the 32-tap window
    sum runs as 5 log-doubling shifted adds (gear.windowed_gear_sum).
    """
    h = gear.windowed_gear_sum(gear.mix32_jnp(x))[:, gear.GEAR_WINDOW - 1 :]
    lanes = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)

    def pack(bits):
        return jnp.sum(
            bits.reshape(-1, n // 32, 32).astype(jnp.uint32) * lanes, axis=-1
        )

    return pack((h & mask_s) == 0), pack((h & mask_l) == 0)


def _unpack_positions(words: np.ndarray, valid_len: int) -> np.ndarray:
    """uint32 packed bitmap → sorted candidate positions < valid_len."""
    bits = np.unpackbits(words.view(np.uint8), bitorder="little")
    pos = np.nonzero(bits)[0]
    return pos[pos < valid_len]


def _cpu_count() -> int:
    import os

    return os.cpu_count() or 4


def _map_threads(fn, items: list, min_batch: int = 2) -> list:
    """Thread-pool map for GIL-dropping work (native ctypes calls, hashlib
    over large buffers); sequential below ``min_batch``."""
    if len(items) < min_batch:
        return [fn(i) for i in items]
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=min(32, _cpu_count())) as pool:
        return list(pool.map(fn, items))


def _grouped_native_digests(
    items: list[tuple[np.ndarray, int, int]], native_fn
) -> list[bytes]:
    """Fan (array, offset, size) items out to GIL-dropping native batch calls.

    Groups runs of extents sharing a source array, then splits long runs
    into ~cpu_count sub-groups so one large stream still fans out across
    cores (each sub-group is an independent native call; order-preserving
    concat keeps digest order). ``native_fn(arr, extents_i64) -> bytes``
    is the 32-B-per-extent batch contract shared by ntpu_sha256_many and
    ntpu_blake3_many.
    """
    groups: list[tuple[np.ndarray, list[tuple[int, int]]]] = []
    for arr, off, size in items:
        if groups and groups[-1][0] is arr:
            groups[-1][1].append((off, size))
        else:
            groups.append((arr, [(off, size)]))
    ncpu = _cpu_count()
    if ncpu > 1 and len(groups) < ncpu:
        per = max(8, -(-len(items) // ncpu))
        groups = [
            (arr, exts[i : i + per])
            for arr, exts in groups
            for i in range(0, len(exts), per)
        ]
    flat = _map_threads(
        lambda g: native_fn(g[0], np.asarray(g[1], dtype=np.int64)), groups
    )
    return [
        blob[32 * i : 32 * (i + 1)] for blob in flat for i in range(len(blob) // 32)
    ]


def _host_digests(items: list[tuple[np.ndarray, int, int]]) -> list[bytes]:
    """Threaded host SHA-256 over (array, offset, size) extents.

    Routes through the native SHA-NI batch call when the engine is built
    (≥ 8 items: below that hashlib — which also drops the GIL for buffers
    > 2 KiB — beats the FFI round trip); both arms scale across cores
    (the crossover arm for small batches where the device scan is
    latency-bound).
    """
    import hashlib

    from nydus_snapshotter_tpu.ops import native_cdc

    lib = native_cdc.load()
    if lib is not None and hasattr(lib, "ntpu_sha256_many") and len(items) >= 8:
        return _grouped_native_digests(items, native_cdc.sha256_many_native)

    def one(item: tuple[np.ndarray, int, int]) -> bytes:
        arr, off, size = item
        return hashlib.sha256(memoryview(arr)[off : off + size]).digest()

    return _map_threads(one, items, min_batch=8)


def _host_digests_blake3(items: list[tuple[np.ndarray, int, int]]) -> list[bytes]:
    """Threaded host BLAKE3 over (array, offset, size) extents.

    Same fan-out as :func:`_host_digests` via the shared grouped-batch
    helper, hashing with the native blake3 arm (ntpu_blake3_many) when the
    engine is built — with no minimum-batch gate, because the fallback is
    the pure-Python spec implementation (~3 orders slower than hashlib, so
    the FFI round trip always wins). Needed when packing with
    ``digester="blake3"`` so chunk digests match the reference toolchain's
    default and dedup against REAL nydus images gets content hits
    (reference tool/builder.go:122-123 chunk-dict probes are digest-keyed).
    """
    from nydus_snapshotter_tpu.ops import native_cdc

    lib = native_cdc.load()
    if lib is not None and hasattr(lib, "ntpu_blake3_many"):
        return _grouped_native_digests(items, native_cdc.blake3_many_native)

    from nydus_snapshotter_tpu.utils import blake3 as pyb3

    def one(item: tuple[np.ndarray, int, int]) -> bytes:
        arr, off, size = item
        return pyb3.blake3(bytes(memoryview(arr)[off : off + size]))

    return _map_threads(one, items, min_batch=8)


def host_digests_for(digester: str):
    """The (array, offset, size)-extents digest fan-out for an algorithm —
    the single selector pack paths use instead of branching inline."""
    return _host_digests_blake3 if digester == "blake3" else _host_digests


class ChunkDigestEngine:
    """Chunk + digest byte streams on device (or numpy for differential runs).

    Parameters mirror the reference's PackOption knobs: ``chunk_size``
    (power-of-two average; pkg/converter/types.go:76-79) and the chunking
    mode — ``cdc`` (content-defined, the accel feature) or ``fixed`` (nydus
    default fixed-size chunks).
    """

    def __init__(
        self,
        chunk_size: int = 0x100000,
        mode: str = "cdc",
        backend: str = "jax",
        window: int = DEFAULT_WINDOW,
        digest_backend: str | None = None,
        digester: str = "sha256",
    ):
        if mode not in ("cdc", "fixed"):
            raise ValueError(f"unknown chunking mode {mode!r}")
        if backend not in ("jax", "numpy", "hybrid", "fused"):
            raise ValueError(f"unknown backend {backend!r}")
        if window % 32:
            raise ValueError("window must be a multiple of 32")
        self.chunk_size = chunk_size
        self.mode = mode
        self.backend = backend
        self.window = window
        # hybrid: native/sequential boundaries + threaded host SHA — the
        # latency arm of the crossover (device kernels win only on bulk
        # batches; SURVEY §7 hard-part #3 fallback)
        self.digest_backend = digest_backend or (
            "host" if backend == "hybrid" else "jax" if backend == "fused" else backend
        )
        if self.digest_backend not in ("jax", "numpy", "host"):
            raise ValueError(f"unknown digest backend {self.digest_backend!r}")
        if digester not in ("sha256", "blake3"):
            raise ValueError(f"unknown digester {digester!r}")
        # blake3 = the reference toolchain's default chunk digester
        # (RafsSuperFlags HASH_BLAKE3). digest_backend="jax" routes blake3
        # through the device tree kernel (_digests_bucketed_b3 /
        # ops/blake3_jax); other backends use the host arm (native
        # ntpu_blake3_many / pure-Python spec impl). The SHA-NI *fused*
        # chunk+digest arms are sha-specific and gate off (_fused_available).
        self.digester = digester
        self.params = cdc.CDCParams(chunk_size) if mode == "cdc" else None

    # -- boundaries ---------------------------------------------------------

    def boundaries(self, data: bytes | np.ndarray) -> np.ndarray:
        """Cut offsets for one stream (exclusive ends, last == len)."""
        arr = np.frombuffer(data, dtype=np.uint8) if isinstance(data, (bytes, bytearray)) else data
        if self.mode == "fixed":
            return cdc.chunk_fixed(arr.size, self.chunk_size)
        if arr.size == 0:
            return np.asarray([], dtype=np.int64)
        if self.backend == "hybrid":
            from nydus_snapshotter_tpu.ops import native_cdc

            if native_cdc.available():
                # chunk_data_best: vectorized striped table scan when the
                # [compression] vectorized knob allows it and the arm is
                # built, sequential otherwise — cut-identical either way.
                return native_cdc.chunk_data_best(arr, self.params)
            return cdc.chunk_data_np(arr, self.params)
        if self.backend == "numpy":
            return cdc.chunk_data_np(arr, self.params)
        cand_s, cand_l = self._candidates_windowed(arr)
        return cdc.resolve_cuts(cand_s, cand_l, arr.size, self.params)

    # Smallest device window: the Pallas kernel's lane*tile granularity
    # (ops/gear_pallas.py); also bounds distinct compiled shapes.
    MIN_WINDOW = 1 << 19

    def _dispatch_windows(self, arr: np.ndarray):
        """Enqueue the device hash of one stream; returns an opaque handle
        for :meth:`_collect_windows`. Dispatch is ASYNC (jax queues the
        upload + kernel), so callers can enqueue stream i+1 before
        collecting stream i — the double-buffered infeed discipline: the
        device crunches the next stream while the host unpacks/resolves
        the previous one."""
        # Shrink the window for small streams: a 512 KiB buffer hashed in a
        # fixed 4 MiB window wastes 8x device compute on zero padding (the
        # streaming pack drains ~2*max_size buffers). Power-of-two windows
        # in [MIN_WINDOW, self.window] keep the compile count logarithmic.
        w = min(self.window, max(self.MIN_WINDOW, _pow2_ceil(max(1, arr.size))))
        tail_len = gear.GEAR_WINDOW - 1
        n_windows = (arr.size + w - 1) // w
        # Window rows prefixed with the previous window's 31-byte tail; the
        # final window zero-padded to the static shape. The batch dim is
        # padded to a power of two so XLA compiles O(log) distinct shapes,
        # not one per stream length.
        n_rows = _pow2_ceil(n_windows)
        rows = np.zeros((n_rows, tail_len + w), dtype=np.uint8)
        for i in range(n_windows):
            lo = i * w
            hi = min(lo + w, arr.size)
            rows[i, tail_len : tail_len + hi - lo] = arr[lo:hi]
            if lo:
                rows[i, :tail_len] = arr[lo - tail_len : lo]
        from nydus_snapshotter_tpu.ops import gear_pallas

        if gear_pallas.supported(w):
            bm_s, bm_l = gear_pallas.gear_bitmaps(
                jnp.asarray(rows), self.params.mask_small, self.params.mask_large, w
            )
        else:
            bm_s, bm_l = _hash_bitmaps_kernel(
                jnp.asarray(rows),
                jnp.uint32(self.params.mask_small),
                jnp.uint32(self.params.mask_large),
                w,
            )
        return bm_s, bm_l, w, n_windows

    def _collect_windows(
        self, handle, arr: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        bm_s, bm_l, w, n_windows = handle
        bm_s, bm_l = np.asarray(jax.device_get(bm_s)), np.asarray(jax.device_get(bm_l))
        parts_s, parts_l = [], []
        for i in range(n_windows):
            valid = min(w, arr.size - i * w)
            parts_s.append(_unpack_positions(bm_s[i], valid) + i * w)
            parts_l.append(_unpack_positions(bm_l[i], valid) + i * w)
        return np.concatenate(parts_s), np.concatenate(parts_l)

    def _candidates_windowed(self, arr: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        return self._collect_windows(self._dispatch_windows(arr), arr)

    # -- digesting ----------------------------------------------------------

    def digests(self, data: bytes | np.ndarray, cuts: np.ndarray) -> list[bytes]:
        arr = np.frombuffer(data, dtype=np.uint8) if isinstance(data, (bytes, bytearray)) else data
        extents = cdc.cuts_to_extents(cuts)
        if self.digester == "blake3":
            items = [(arr, o, s) for o, s in extents]
            if self.digest_backend == "jax":
                return self._digests_bucketed_b3(items)
            return _host_digests_blake3(items)
        if self.digest_backend == "numpy":
            import hashlib

            return [hashlib.sha256(arr[o : o + s].tobytes()).digest() for o, s in extents]
        if self.digest_backend == "host":
            return _host_digests([(arr, o, s) for o, s in extents])
        return self._digests_bucketed(arr, extents)

    def _digests_bucketed(self, arr: np.ndarray, extents: list[tuple[int, int]]) -> list[bytes]:
        """Bucket chunks by power-of-two padded block count, digest per bucket."""
        out: list[bytes | None] = [None] * len(extents)
        if not extents:
            return []
        # Power-of-two capacity classes bound the number of compiled shapes;
        # clamping to the engine's static max chunk size stops the top class
        # from doubling the scan length (a max_size chunk is 65537 blocks —
        # rounding to 131072 would double compile and run time) while keeping
        # shapes identical across calls.
        max_chunk = self.params.max_size if self.params else self.chunk_size
        max_blocks = sha256.n_padded_blocks(max_chunk)
        buckets: dict[int, list[int]] = {}
        for idx, (_off, size) in enumerate(extents):
            nb = sha256.n_padded_blocks(size)
            cap = min(1 << (nb - 1).bit_length() if nb > 1 else 1, max_blocks)
            buckets.setdefault(cap, []).append(idx)
        for cap, idxs in sorted(buckets.items()):
            msgs = [arr[extents[i][0] : extents[i][0] + extents[i][1]].tobytes() for i in idxs]
            blocks, counts = sha256.pack_messages_np(msgs, block_capacity=cap)
            # Pad the batch dim to a power of two (dummy rows have zero
            # blocks, so the scan leaves them at H0 and they're discarded) —
            # bounds compile count like the window batching above.
            m_pad = _pow2_ceil(len(msgs)) - len(msgs)
            if m_pad:
                blocks = np.concatenate([blocks, np.zeros((m_pad, cap, 16), np.uint32)])
                counts = np.concatenate([counts, np.zeros(m_pad, np.int32)])
            states = np.asarray(
                jax.device_get(sha256.sha256_batch(jnp.asarray(blocks), jnp.asarray(counts)))
            )
            for row, i in enumerate(idxs):
                out[i] = sha256.digest_to_bytes(states[row])
        return out  # type: ignore[return-value]

    def _digests_bucketed_b3(
        self, items: list[tuple[np.ndarray, int, int]]
    ) -> list[bytes]:
        """Device BLAKE3: bucket chunks by power-of-two leaf count, digest
        per bucket (ops/blake3_jax — leaves parallel across lanes, log-depth
        tree merge). The blake3 analog of :meth:`_digests_bucketed`; takes
        (array, offset, size) items so call sites hand over zero-copy views
        (the only copy is pack_messages_np's write into the padded batch)."""
        from nydus_snapshotter_tpu.ops import blake3_jax

        out: list[bytes | None] = [None] * len(items)
        if not items:
            return []
        max_chunk = self.params.max_size if self.params else self.chunk_size
        max_leaves = _pow2_ceil(blake3_jax.n_leaves(max_chunk))
        buckets: dict[int, list[int]] = {}
        for idx, (_arr, _off, size) in enumerate(items):
            cap = min(_pow2_ceil(blake3_jax.n_leaves(size)), max_leaves)
            buckets.setdefault(cap, []).append(idx)
        for cap, idxs in sorted(buckets.items()):
            msgs = [items[i][0][items[i][1] : items[i][1] + items[i][2]] for i in idxs]
            blocks, lengths = blake3_jax.pack_messages_np(msgs, leaf_capacity=cap)
            m_pad = _pow2_ceil(len(msgs)) - len(msgs)
            if m_pad:
                blocks = np.concatenate(
                    [blocks, np.zeros((m_pad,) + blocks.shape[1:], np.uint32)]
                )
                lengths = np.concatenate([lengths, np.zeros(m_pad, np.int32)])
            words = np.asarray(
                jax.device_get(
                    blake3_jax.blake3_batch(jnp.asarray(blocks), jnp.asarray(lengths))
                )
            )
            for row, i in enumerate(idxs):
                out[i] = blake3_jax.digest_to_bytes(words[row])
        return out  # type: ignore[return-value]

    def boundaries_many(self, arrs: list[np.ndarray]) -> list[np.ndarray]:
        """Per-stream cut offsets for many streams (thread-parallel on the
        hybrid backend: the native chunker drops the GIL)."""
        if self.backend == "hybrid":
            return _map_threads(self.boundaries, arrs)
        if self.backend == "jax" and self.mode == "cdc":
            # Double-buffered device sweep: keep at most DEPTH streams
            # in flight (async dispatch), collecting/resolving in order —
            # the device works on stream i+1 while the host resolves
            # stream i, with device/host memory bounded at DEPTH streams
            # instead of the whole batch.
            DEPTH = 2
            from collections import deque

            nonempty = deque((i, a) for i, a in enumerate(arrs) if a.size)
            inflight: deque = deque()
            out: list[np.ndarray] = [
                np.asarray([], dtype=np.int64) for _ in arrs
            ]
            while nonempty or inflight:
                while nonempty and len(inflight) < DEPTH:
                    i, a = nonempty.popleft()
                    inflight.append((i, a, self._dispatch_windows(a)))
                i, a, h = inflight.popleft()
                cand_s, cand_l = self._collect_windows(h, a)
                out[i] = cdc.resolve_cuts(cand_s, cand_l, a.size, self.params)
            return out
        return [self.boundaries(a) for a in arrs]

    def digest_all(
        self,
        arrs: list[np.ndarray],
        per_file_extents: list[list[tuple[int, int]]],
    ) -> list[bytes]:
        """Flat digests for pre-computed per-file extents, in file order.

        One global pass across every file — a single bucketed device batch
        or one host thread-pool sweep, instead of a tiny batch per file.
        """
        if not arrs:
            return []
        if self.digester == "blake3":
            items = [
                (arr, o, s)
                for arr, extents in zip(arrs, per_file_extents)
                for o, s in extents
            ]
            if self.digest_backend == "jax":
                return self._digests_bucketed_b3(items)
            return _host_digests_blake3(items)
        if self.digest_backend == "host":
            return _host_digests(
                [
                    (arr, o, s)
                    for arr, extents in zip(arrs, per_file_extents)
                    for o, s in extents
                ]
            )
        if self.digest_backend == "numpy":
            import hashlib

            return [
                hashlib.sha256(arr[o : o + s].tobytes()).digest()
                for arr, extents in zip(arrs, per_file_extents)
                for o, s in extents
            ]
        # one global bucketed device batch across every file
        offsets = []
        total = 0
        for arr in arrs:
            offsets.append(total)
            total += arr.size
        joined = np.concatenate(arrs) if len(arrs) > 1 else arrs[0]
        flat_extents = [
            (off + o, s)
            for off, extents in zip(offsets, per_file_extents)
            for o, s in extents
        ]
        return self._digests_bucketed(joined, flat_extents)

    def digest_many(self, datas: list[bytes]) -> list[bytes]:
        """Batched digests of pre-delimited chunks (no CDC) — the tarfs /
        index build sources, where boundaries come from the tar layout."""
        if not datas:
            return []
        if self.digester == "blake3":
            items = [(np.frombuffer(d, dtype=np.uint8), 0, len(d)) for d in datas]
            if self.digest_backend == "jax":
                return self._digests_bucketed_b3(items)
            return _host_digests_blake3(items)
        if self.digest_backend == "numpy":
            import hashlib

            return [hashlib.sha256(d).digest() for d in datas]
        if self.digest_backend == "host":
            return _host_digests(
                [(np.frombuffer(d, dtype=np.uint8), 0, len(d)) for d in datas]
            )
        arr = np.frombuffer(b"".join(datas), dtype=np.uint8)
        extents = []
        off = 0
        for d in datas:
            extents.append((off, len(d)))
            off += len(d)
        return self._digests_bucketed(arr, extents)

    # -- end to end ---------------------------------------------------------

    def process(self, data: bytes | np.ndarray) -> list[ChunkMeta]:
        """Chunk one stream and digest every chunk."""
        arr = np.frombuffer(data, dtype=np.uint8) if isinstance(data, (bytes, bytearray)) else data
        cuts = self.boundaries(arr)
        digests = self.digests(arr, cuts)
        return [
            ChunkMeta(offset=o, size=s, digest=d)
            for (o, s), d in zip(cdc.cuts_to_extents(cuts), digests)
        ]

    def process_many(self, streams: list[bytes]) -> list[list[ChunkMeta]]:
        """Per-file chunking (nydus chunks each file independently).

        Boundaries run per stream (thread-parallel on the hybrid backend:
        the native chunker drops the GIL), then ALL chunks are digested in
        one global pass — a single big device batch or one host thread-pool
        sweep, instead of a tiny batch per file.
        """
        if not streams:
            return []
        arrs = [
            np.frombuffer(s, dtype=np.uint8) if isinstance(s, (bytes, bytearray)) else s
            for s in streams
        ]
        if self.backend == "fused" and self.mode == "cdc":
            out = self._process_many_device_fused(arrs)
            if out is not None:
                return out
        if self._fused_available():
            return self._process_many_fused(arrs)
        all_cuts = self.boundaries_many(arrs)

        per_file_extents = [cdc.cuts_to_extents(c) for c in all_cuts]
        flat_digests = self.digest_all(arrs, per_file_extents)
        out: list[list[ChunkMeta]] = []
        pos = 0
        for extents in per_file_extents:
            metas = [
                ChunkMeta(offset=o, size=s, digest=flat_digests[pos + i])
                for i, (o, s) in enumerate(extents)
            ]
            pos += len(extents)
            out.append(metas)
        return out

    def _fused_available(self) -> bool:
        """Single-pass native chunk+digest (SIMD bitmaps + SHA-NI): the
        host latency arm's fast path — chunk bytes digested cache-warm,
        one GIL-dropping call per stream."""
        if not (
            self.mode == "cdc"
            and self.backend == "hybrid"
            and self.digest_backend == "host"
            # the fused arm digests with SHA-NI or 8-way-AVX2 blake3; both
            # route through the native algo dispatch (ntpu_chunk_digest)
            and self.digester in ("sha256", "blake3")
        ):
            return False
        from nydus_snapshotter_tpu.ops import native_cdc

        return native_cdc.chunk_digest_available()

    def _process_many_device_fused(
        self, arrs: list[np.ndarray]
    ) -> list[list[ChunkMeta]] | None:
        """Full-path device composition (ops/fused_convert): the whole
        batch as two device dispatches — gear+compaction, then
        gather+digest — with only candidate/cut metadata on the host.
        Returns None on candidate-capacity overflow (pathological input)
        so process_many falls through to the windowed device path."""
        from nydus_snapshotter_tpu.ops import fused_convert

        eng = fused_convert.FusedDeviceEngine(
            chunk_size=self.chunk_size, digester=self.digester
        )
        try:
            res = eng.process_many(arrs)
        except fused_convert.FusedOverflow:
            fused_convert.record_host_fallback()
            return None
        return [
            [
                ChunkMeta(offset=o, size=s, digest=d)
                for (o, s), d in zip(cdc.cuts_to_extents(cuts), digests)
            ]
            for cuts, digests in zip(res.cuts, res.digests)
        ]

    def _process_many_fused(self, arrs: list[np.ndarray]) -> list[list[ChunkMeta]]:
        from nydus_snapshotter_tpu.ops import native_cdc

        def one(arr: np.ndarray) -> list[ChunkMeta]:
            cuts, digests = native_cdc.chunk_digest_native(
                arr, self.params, digester=self.digester
            )
            start = 0
            metas = []
            for i, c in enumerate(cuts):
                metas.append(
                    ChunkMeta(
                        offset=start,
                        size=int(c) - start,
                        digest=digests[32 * i : 32 * (i + 1)],
                    )
                )
                start = int(c)
            return metas

        return _map_threads(one, arrs)
