"""Fused device full-path convert: gear → cuts → gather → digest → probe.

The composition the isolated kernel benchmarks don't prove: one device
program per phase, with only KILOBYTES of metadata crossing the host
boundary between them. The multi-GiB corpus is uploaded (or generated)
on device ONCE and never comes back:

- **Pass 1 (one jit dispatch).** Gear candidate bitmaps over the whole
  buffer (ops/gear_pallas on TPU, the XLA formulation elsewhere), then
  ON-DEVICE sparse compaction: word-level ``lax.population_count`` →
  ``nonzero`` over words → bit expansion. D2H is the candidate position
  list (~KBs at real mask densities), not the N/32-byte bitmaps.
- **Host middle (microseconds).** FastCDC cut resolution over the sparse
  candidates per file (ops/cdc.resolve_cuts — O(chunks·log cands)) and
  the bucket plan (power-of-two block-capacity classes, exact counts).
  Shipping cuts through the host costs two dispatch floors but buys
  EXACT static shapes for pass 2 — an on-device resolver would force
  worst-case (~16x padded) digest compute, which loses at any batch size.
- **Pass 2 (one jit dispatch).** It reads the buffer as 32-bit words
  (``lane_words``: the same host memory, uploaded a second time under
  pass 1). Per bucket: ``lax.scan`` of ``dynamic_slice`` gathers of words,
  funnel-shifted to the chunk's byte-exact start (``_chunk_words``: no
  realignment kernel, nothing touched as a byte), SHA-256 padding applied
  with masks on a word iota, the rows transposed once so that the digest
  scan reads a ``[16, rows]`` block a step (``sha256._sha256_lanes``), and
  the chunk-dict probe
  (parallel/sharded_dict._probe_local) over every digest. D2H is
  32 B/chunk of digests + 4 B/chunk of dict hits. A class is gathered
  and digested as ONE batch of rows while that batch stays within
  TILE_BYTES; a wider one runs as **row tiles**: equal power-of-two
  slices of its rows, one after the other through one compiled loop
  body, so that pass 2's HBM follows the budget and not the layer.

A lane buffer is addressed with int32, so its padded length stays under
ADDRESS_LIMIT (2 GiB). A layer that pads past it is packed as **batches of
whole files** (plan_batches, FusedDeviceEngine.process_batches): each batch
is the two dispatches above on a buffer joined on the device from runs of
the one host buffer, the next batch's upload and pass 1 enqueued before
this one's candidates are waited for.

Why two dispatches and not one: the digest stage's shapes depend on the
resolved cuts. Keeping resolution on device would make bucket geometry
dynamic, forcing every chunk slot to the 4 MiB max class. Two dispatch
floors on a multi-GiB batch are the price.

Replaces the one-process hot loop of the reference's ``nydus-image
create`` (chunk+digest+dedup inside pkg/converter/tool/builder.go:148-178;
the chunk-dict probe at builder.go:122-123).

Differential oracle: ChunkDigestEngine(backend="numpy") — the fused path
must produce byte-identical cuts and digests (tests/test_fused_convert.py).
"""

from __future__ import annotations

import bisect
import functools
import itertools
from dataclasses import dataclass
from time import perf_counter

import jax
import jax.numpy as jnp
import numpy as np

from nydus_snapshotter_tpu.ops import cdc, gear, sha256

WINDOW = 1 << 22  # pass-1 hash window (matches ops/chunker.DEFAULT_WINDOW)
TAIL = gear.GEAR_WINDOW - 1


class FusedOverflow(RuntimeError):
    """The batch does not fit the lane: a pathological input exceeds the
    candidate capacity, or its buffer pads past int32 addressing. A layer
    past that limit is packed as batches of whole files (plan_batches,
    process_batches), so what still raises on the served path is ONE file
    whose own bytes pad past it; the converter counts it
    (record_host_fallback) and redoes the layer on its per-file lane."""


def _counters():
    """(dispatches, bytes, stage_seconds{stage}, host_fallbacks): the
    fused-convert counters next to the pipeline's
    (ntpu_convert_pipeline_*). Stages: layout = the lane buffer made
    ready (the extent table; a host copy only where the input is not
    already one padded buffer), h2d = upload, pass1_gear =
    gear+compaction dispatch and candidate D2H, host_resolve = cut resolution + bucket plan (the
    host arm between dispatches), pass2_digest = gather+digest+probe
    dispatch, digest_d2h = digest states (and probe) back to the host as
    per-chunk bytes. Fed from the ``pack:lane.*`` spans' own times
    (process_many): nothing is timed twice."""
    from nydus_snapshotter_tpu.metrics import registry as _metrics

    # literal Counter(...) calls: tools/analyze.py's metric drift gate
    # reads the names from the AST
    register = _metrics.default_registry.register
    return (
        register(
            _metrics.Counter(
                "ntpu_fused_convert_dispatches",
                "Fused device convert batches dispatched",
            )
        ),
        register(
            _metrics.Counter(
                "ntpu_fused_convert_bytes",
                "Bytes processed by fused device convert batches",
            )
        ),
        register(
            _metrics.Counter(
                "ntpu_fused_convert_stage_seconds",
                "Wall seconds per fused-convert stage",
                ("stage",),
            )
        ),
        register(
            _metrics.Counter(
                "ntpu_fused_convert_host_fallbacks",
                "Fused batches that overflowed and were redone on the host lanes",
            )
        ),
    )


def _early_start_counter():
    """Batches whose upload and pass 1 a caller had enqueued ahead of its
    own host work (FusedDeviceEngine.begin) and that process_many then
    finished; a begun lane that was dropped counts nowhere, and neither
    do the batches of a split layer, which process_batches begins itself
    once the caller's scan has given their files (Begun.early). Beside
    _counters(), whose four the benchmark and chip_smoke.py unpack by
    position."""
    from nydus_snapshotter_tpu.metrics import registry as _metrics

    return _metrics.default_registry.register(
        _metrics.Counter(
            "ntpu_fused_convert_early_starts_total",
            "Fused batches begun by their caller ahead of its host work and finished",
        )
    )


def _split_packs_counter():
    """Layers that no one lane buffer held and that process_batches packed
    as several batches of whole files; each batch is a dispatch of its own
    in _counters(). Its own accessor for the same reason as
    _early_start_counter()."""
    from nydus_snapshotter_tpu.metrics import registry as _metrics

    return _metrics.default_registry.register(
        _metrics.Counter(
            "ntpu_fused_convert_split_packs_total",
            "Layers past one lane buffer that were packed as several device batches of whole files",
        )
    )


def _record_dispatch(n_bytes: int, stage_seconds: dict[str, float], early_start: bool = False) -> None:
    disp, by_bytes, busy, _ = _counters()
    disp.inc()
    by_bytes.inc(n_bytes)
    _early_start_counter().inc(int(early_start))
    for stage, seconds in stage_seconds.items():
        busy.labels(stage).inc(seconds)


def record_host_fallback() -> None:
    """A caller swallowed FusedOverflow and is redoing the batch on its
    host lanes: counted, so a run that meant to use the device can tell."""
    _counters()[3].inc()


def _pow2_ceil(n: int) -> int:
    return 1 << (n - 1).bit_length() if n > 1 else 1


def _pow2_floor(n: int) -> int:
    return 1 << (n.bit_length() - 1) if n >= 1 else 0


# The fewest rows a digest class is dispatched with. A digest batch costs
# its class's cap_blocks serial steps whatever its rows, and on the v5e a
# step of the unrolled sha256 scan takes 60.5-61.3 us with ONE row (the
# chip's compiler then fuses all 64 rounds into one kernel over [1,1]
# arrays) and 4.6-4.8 us with 2, 4 or 8: in _pass2 a one-chunk class of
# 65,536 blocks took 3.97 s as one row and 0.33 / 0.33 / 0.35 s padded
# to 2 / 4 / 8 (PERF.md section 5; my chip runs, PR 27). So 2, the
# smallest batch past the cliff: a padding row is gathered and digested
# like any other, which is nearly free on the chip up to ~128 rows but
# costs the CPU backend, whose scan is bound by throughput, a whole row's
# time. In the lane-dense loop (sha256._sha256_lanes) a step takes 5.6 us
# at 2 rows, 2.7 at 16 and 2.9 at 128, and the gather 40 us a row of 2
# MiB: the one-chunk class takes 0.368 s at 2 rows and 0.176 at 16 (my
# chip run, PR 34), so a floor of 16 is the chip's optimum and the CPU
# backend's cost; the value is the plan's, and stays.
ROW_FLOOR = 2


def bucket_rows(live: int) -> int:
    """Rows a digest class holding ``live`` chunks is dispatched with:
    the next power of two, and never fewer than ROW_FLOOR. The one rule
    for the row axis of every pass-2 batch: plan_buckets takes it through
    class_rows, which leaves a batch within TILE_BYTES as this gives it
    and runs a wider one in tiles; the per-device rows of
    ops/mesh_pack.plan_mesh_pack are these, untiled (no served verb
    reaches a mesh)."""
    return max(ROW_FLOOR, _pow2_ceil(live))


# The most bytes of gathered blocks (rows x the bytes a row gathers) that
# pass 2 holds as ONE batch; a wider class runs in row tiles (class_rows).
# _pass2's temporaries are three bytes a byte of its widest batch (the
# gathered rows, their transpose, the digest's blocks): for one class of
# 32,768 blocks in a 1,280 MiB buffer 1,538 MiB at 256 rows, tiled or as
# one batch, and 6,146 MiB at the 1,024 rows that bucket_rows gives the 576
# chunks of 1-2 MiB in the jax / jaxlib / libtpu layer of a training image
# (my chip run, PR 34, and tests/test_chip_compile.py). The value dates
# from the byte gather, whose batch u32[rows, cap_blocks, 16] the chip's
# compiler laid out eightfold (16 words on 128 lanes: ten bytes a byte,
# 5,122 MiB at 256 rows, RESOURCE_EXHAUSTED at 1,024; PR 33): 512 MiB, 256
# such rows, was the largest power of two whose temporaries stayed under
# half the HBM beside that buffer, and is still the smallest that leaves
# every layer the benchmark had before as it was (node:21 at 64 KiB chunks:
# 4,096 rows x 2,048 blocks = 512 MiB, one batch), which is why it stays:
# a plan change is judged on that cell. Time asks for neither more nor
# less: a tile of 256 such rows takes 0.149 s, 0.140 of it the digest's
# 32,768 serial steps (4.3 us a step; 5.0 at 512 rows, PR 33) and 0.01 the
# gather, 40 us a row of 2 MiB, a padding row like a live one (it was 1.9
# ms); the 576 chunks take 0.445 s in 3 x 256 rows, where the byte gather
# took 1.80 (my chip runs, PR 34; PERF.md section 6).
TILE_BYTES = 512 << 20


def class_rows(live: int, row_bytes: int) -> tuple[int, int]:
    """-> (rows, tile_rows) of a digest class holding ``live`` chunks whose
    rows gather ``row_bytes`` each. While bucket_rows(live) rows stay
    within TILE_BYTES the class is one batch of them (tile_rows == rows):
    the rule the row axis had before there were tiles. A wider class is
    run in tiles of the largest power-of-two row count within the budget,
    as many as its chunks need and no more: ceil(live / tile_rows) of
    them, not the next power of two of its rows."""
    rows = bucket_rows(live)
    if rows * row_bytes <= TILE_BYTES:
        return rows, rows
    # never under the floor, though one row alone be over the budget
    tile = max(ROW_FLOOR, _pow2_floor(TILE_BYTES // row_bytes))
    return -(-live // tile) * tile, tile


# Device ints are 32-bit (no x64): pass 2's chunk offsets address a lane
# buffer with int32, so a buffer's padded length stays under this, and a
# layer that pads past it is packed as several batches (plan_batches). A
# constant of the device, no option: a test reaches the split at a few
# MiB by patching it.
ADDRESS_LIMIT = 1 << 31


def padded_length(total: int, max_size: int) -> int:
    """Bytes of the lane's device buffer for ``total`` bytes of input cut
    into chunks of at most ``max_size``: the one padding rule of the
    lane (layout, lane_buffer, a batch of a split layer, and a caller that
    reads a layer straight into a buffer of this size). Raises
    FusedOverflow for a ``total`` that pads to ADDRESS_LIMIT or past it."""
    # a window multiple + one max-chunk guard so pass-2 dynamic_slice
    # never clamps a start (clamping would shift the slice and corrupt
    # in-range bytes). The gather reads whole words, one past its class's
    # capacity: max_size + 64 is the widest class's, and a chunk of that
    # class is longer than the word, so the guard covers it
    guard = max_size + 64
    npad = -(-max(1, total + guard) // WINDOW) * WINDOW
    # quantize to 1/8-pow2 steps: bounded compile count without the
    # full pow2 doubling (which would push a 1.1 GiB batch to 2 GiB)
    step = max(WINDOW, _pow2_ceil(npad) // 8)
    npad = -(-npad // step) * step
    # A batch is one layer where the layer fits: 640 MiB for half of
    # node:21, 1,280 MiB for the jax/jaxlib/libtpu layer of a training
    # image (benchmark/configs/mlimage-1m.json). The pip install tensorflow
    # layer (2,047 MiB of tar, one file of 1,046 MiB:
    # benchmark/configs/tfimage-1m.json) would pad to 2,560 MiB:
    # process_batches splits it at whole files into batches that pass here.
    if npad >= ADDRESS_LIMIT:
        raise FusedOverflow(
            f"batch of {total} bytes pads to {npad} — beyond int32 "
            "device addressing; split the batch"
        )
    return npad


def lane_fits(total: int, max_size: int) -> bool:
    """Whether ONE lane buffer holds ``total`` bytes: padded_length's limit
    as a question, for whoever has another way to go (batches)."""
    try:
        padded_length(total, max_size)
    except FusedOverflow:
        return False
    return True


def zeroed_buffer(npad: int) -> np.ndarray:
    """u8[npad] of zeros on a page boundary, no page of it touched yet.

    The boundary is for whoever fills it through the kernel: a 523 MiB
    file read into a destination 16 bytes past a page boundary (where
    glibc's chunk header leaves np.zeros) took 0.61-0.68 s on the v5e
    machine's host, into an aligned one 0.43-0.48 s, which is what
    f.read() takes (PERF.md section 6; my chip runs, PR 30)."""
    page = 4096
    big = np.zeros(npad + page, dtype=np.uint8)
    start = -big.ctypes.data % page
    return big[start : start + npad]


def lane_buffer(data: np.ndarray, npad: int) -> tuple[np.ndarray, int]:
    """-> (u8[npad] that starts with ``data``, bytes copied to make it).

    Where the array behind ``data`` has room for ``npad`` bytes from
    data's first on (a layer read into the head of
    zeroed_buffer(padded_length(...))), that stretch is the buffer as it
    stands: nothing is copied and no page is touched twice. Else one bulk
    copy into a fresh zeroed buffer. What follows ``data`` is never
    judged (pass 1 drops candidate words past the valid length, pass 2
    masks every gather by its chunk's size), so the two give the same
    cuts and digests; zeros keep the upload a function of the input.
    """
    owner = data.base
    if (
        isinstance(owner, np.ndarray)
        and owner.dtype == np.uint8
        and owner.ndim == 1
        and owner.flags.c_contiguous
        and data.flags.c_contiguous
    ):
        start = data.ctypes.data - owner.ctypes.data
        if 0 <= start and start + npad <= owner.size:
            return owner[start : start + npad], 0
    buf = zeroed_buffer(npad)
    buf[: data.size] = data
    return buf, data.size


def lane_words(buf: np.ndarray) -> np.ndarray:
    """u8[..., L] -> u32[..., ceil(L / 4)]: a lane buffer as the words pass 2
    gathers from (_chunk_words), word i holding bytes 4i..4i+3 with the
    first in its low bits. ``"<u4"``, so on a little-endian host (every one
    this runs on) it is a view: the same memory, nothing copied. Every
    padded_length is whole words (a WINDOW multiple); a buffer that is not
    (a mesh slab of any length, ops/mesh_pack) is first padded with zeros.

    The words are made here, on the host, because the chip's compiler
    cannot make them from the u8 operand: ``bitcast_convert_type`` of
    ``buffer.reshape(-1, 4)`` is refused (a last dimension of 4 on 128
    lanes: an allocation of 160 GiB for a 1,280 MiB buffer) and four strided
    slices ``buffer[k::4]`` compile to 3.5 GiB of temporaries and 1.4 TB of
    bytes accessed (compiled for a described v5e, ISSUE 34)."""
    pad = -buf.shape[-1] % 4
    if pad:
        buf = np.pad(buf, [(0, 0)] * (buf.ndim - 1) + [(0, pad)])
    return np.ascontiguousarray(buf).view("<u4")


# ---------------------------------------------------------------------------
# Pass 1: gear bitmaps + on-device candidate compaction
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("mask_s", "mask_l", "wcap_s", "wcap_l")
)
def _pass1(
    buffer: jax.Array,  # u8[NP], NP % WINDOW == 0
    n: jax.Array,  # i32/i64 scalar: valid bytes
    mask_s: int,
    mask_l: int,
    wcap_s: int,
    wcap_l: int,
):
    """-> (sel_s i32[wcap_s], words_s u32[wcap_s], nw_s, … same for _l).

    sel_* are ascending candidate-WORD indices (sentinel: nwords) with
    their raw bitmap words; nw_* are the true candidate-word counts — a
    count > wcap means truncation (FusedOverflow on host).
    """
    npad = buffer.shape[0]
    b = npad // WINDOW
    # windows with 31-byte seam-carry tails (row i prefixed by the last
    # 31 bytes of row i-1; row 0 by zeros — positions < min_size are
    # never judged, so the zeros can't reach a resolved cut)
    main = buffer.reshape(b, WINDOW)
    tails = jnp.concatenate(
        [jnp.zeros((1, TAIL), jnp.uint8), main[:-1, WINDOW - TAIL :]], axis=0
    )
    rows = jnp.concatenate([tails, main], axis=1)  # u8[B, TAIL+WINDOW]

    from nydus_snapshotter_tpu.ops import gear_pallas

    # named scopes: stable names for the parts of the program in a device
    # trace (op-name metadata; not part of the compile cache's key)
    with jax.named_scope("gear"):
        if gear_pallas.supported(WINDOW):
            bm_s, bm_l = gear_pallas.gear_bitmaps(rows, mask_s, mask_l, WINDOW)
        else:
            from nydus_snapshotter_tpu.ops.chunker import _hash_bitmaps_kernel

            bm_s, bm_l = _hash_bitmaps_kernel(
                rows, jnp.uint32(mask_s), jnp.uint32(mask_l), WINDOW
            )

    nwords = npad // 32
    widx_valid = jnp.arange(nwords, dtype=jnp.int32) < (n + 31) // 32

    def compact(bm, wcap):
        # Word indices + raw words, NOT byte positions: word indices stay
        # well inside int32 for any addressable buffer (device ints are
        # 32-bit without x64), and the host expands bit positions in int64.
        words = bm.reshape(nwords)
        # zero whole words beyond the valid length (window padding would
        # otherwise flood the capacity with phantom candidates)
        words = jnp.where(widx_valid, words, jnp.uint32(0))
        pc = jax.lax.population_count(words)
        (sel,) = jnp.nonzero(pc > 0, size=wcap, fill_value=nwords)
        nw = jnp.sum((pc > 0).astype(jnp.int32))
        got = jnp.where(
            sel < nwords, words[jnp.minimum(sel, nwords - 1)], jnp.uint32(0)
        )  # u32[wcap]
        return sel.astype(jnp.int32), got, nw

    with jax.named_scope("compact_s"):
        sel_s, got_s, nw_s = compact(bm_s, wcap_s)
    with jax.named_scope("compact_l"):
        sel_l, got_l, nw_l = compact(bm_l, wcap_l)
    return sel_s, got_s, nw_s, sel_l, got_l, nw_l


def _wcap_for(n: int, density_bits: int, floor: int = 1024) -> int:
    """Static candidate-word capacity: 4x the expected count for a
    2^-density_bits per-position hit rate, floored."""
    expected = max(1, n >> density_bits)
    return _pow2_ceil(max(floor, 4 * expected))


# ---------------------------------------------------------------------------
# Pass 2: gather + SHA pack + digest + dict probe
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Bucket:
    """One power-of-two block-capacity class of the pass-2 plan.

    offsets/sizes are padded to the rows ``class_rows`` gives the class
    (padding rows have size 0 and offset 0 and are discarded on
    assembly); ``count`` is the live prefix and ``blocks`` the digest
    blocks its chunks really hold (of the ``M * cap_blocks`` the class
    computes). ``tile_rows``: the rows pass 2 gathers and digests at a
    time. A class within TILE_BYTES is one batch (``tile_rows == M``,
    M = ``bucket_rows(count)``); a wider one is ``M // tile_rows`` tiles,
    rows ``[t * tile_rows, (t + 1) * tile_rows)`` being tile ``t``, so
    only the last tile holds padding rows. 0 stands for ``M``.
    """

    cap_blocks: int
    offsets: np.ndarray  # i32[M] absolute byte offsets into the buffer
    sizes: np.ndarray  # i32[M]
    count: int
    blocks: int = 0
    tile_rows: int = 0

    @property
    def tiles(self) -> int:
        return len(self.offsets) // self.tile_rows if self.tile_rows else 1


def _chunk_words(words: jax.Array, offs: jax.Array, sizes: jax.Array, n_words: int):
    """-> u32[M, n_words]: row i holds the bytes of the chunk at byte
    offs[i] of the lane buffer as little-endian words, zero from byte
    sizes[i] on. ``words`` is the buffer as lane_words gives it.

    One scan step per chunk, nothing touched as a byte: n_words words from
    the word holding the chunk's first byte and n_words from the next (two
    dynamic_slices: contiguous DMA-shaped copies, not element gathers),
    joined by a funnel shift of 8 * (off & 3) bits, then the tail masked by
    a word iota. The slices read one word past the chunk's capacity, which
    the guard of padded_length (and max_read_span, for a mesh slab) leaves
    room for: a clamped start would shift the whole slice."""
    word_iota = jnp.arange(n_words, dtype=jnp.int32)

    def step(carry, xs):
        off, size = xs
        first = off >> 2
        lo = jax.lax.dynamic_slice(words, (first,), (n_words,))
        hi = jax.lax.dynamic_slice(words, (first + 1,), (n_words,))
        shift = (8 * (off & 3)).astype(jnp.uint32)
        # (hi << 1) << (31 - shift): hi's low bytes on top of lo's high
        # ones, and nothing of hi where the chunk starts on a word (no
        # shift by 32, whose result XLA leaves to the backend)
        got = (lo >> shift) | ((hi << 1) << (31 - shift))
        last = size >> 2  # the word holding byte `size`: its bytes below it stay
        keep = (jnp.uint32(1) << (8 * (size & 3)).astype(jnp.uint32)) - 1
        got = jnp.where(word_iota < last, got, jnp.where(word_iota == last, got & keep, 0))
        return carry, got

    _, rows = jax.lax.scan(step, 0, (offs, sizes))
    return rows


def _gather_pack_sha(words: jax.Array, offs: jax.Array, sizes: jax.Array, cap_blocks: int):
    """Gather chunks at byte-exact offsets and emit SHA-padded blocks in
    the form the digest loop reads (sha256._sha256_lanes): big-endian words
    u32[cap_blocks, 16, M], the chunks on the last axis.

    The 0x80 byte, the byte swap and the two length words are masks and
    shifts on whole u32 words; the rows are transposed once, so that no
    array of the batch's size has a block's 16 words as its last dimension
    (the chip's compiler lays such a one out eightfold, 16 words on 128
    lanes, which was ten bytes of temporaries a byte of the batch: for 256
    rows of 32,768 blocks 5,122 MiB, now 1,538)."""
    n_words = cap_blocks * 16
    word_iota = jnp.arange(n_words, dtype=jnp.int32)
    size = sizes[:, None]
    le = _chunk_words(words, offs, sizes, n_words)
    le = le | jnp.where(
        word_iota == size >> 2, jnp.uint32(0x80) << (8 * (size & 3)).astype(jnp.uint32), 0
    )
    be = (le << 24) | ((le & 0xFF00) << 8) | ((le >> 8) & 0xFF00) | (le >> 24)
    length_at = (size + 8) // 64 * 16 + 14  # the last two words of the last padded block
    be = jnp.where(word_iota == length_at, (size >> 29).astype(jnp.uint32), be)
    be = jnp.where(word_iota == length_at + 1, size.astype(jnp.uint32) << 3, be)
    return be.T.reshape(cap_blocks, 16, -1)


def _gather_pack_b3(words: jax.Array, offs: jax.Array, sizes: jax.Array, cap_leaves: int):
    """Gather chunks into the blake3 batch layout u32[M, C, 16, 16].

    The same gather as the SHA pack and nothing more: blake3's words are
    little-endian, and lengths drive the in-kernel flag/tail handling, so no
    padding bytes or length words are embedded. The hand-over keeps
    blake3_jax's form: its leaves and tree are vmapped over rows, and a
    lane-dense one is more than a reshape there.
    """
    from nydus_snapshotter_tpu.ops import blake3_jax

    le = _chunk_words(words, offs, sizes, cap_leaves * (blake3_jax.LEAF_BYTES // 4))
    return le.reshape(-1, cap_leaves, 16, 16)


def _gather_digest_sha(words: jax.Array, offs, sizes, cap_blocks: int, unroll: bool):
    """One sha256 class of pass 2, gather and digest as the pair they are:
    -> u32[M, 8] states. _pass2's, and the per-device step of
    __graft_entry__.sharded_convert_step's."""
    with jax.named_scope(f"gather_c{cap_blocks}"):
        blocks = _gather_pack_sha(words, offs, sizes, cap_blocks)
    with jax.named_scope(f"sha256_c{cap_blocks}"):
        return sha256._sha256_lanes(blocks, (sizes + 8) // 64 + 1, unroll)


@functools.partial(
    jax.jit,
    static_argnames=(
        "caps", "table_cap", "depth", "digester", "pallas_probe", "probe_interpret"
    ),
)
def _pass2(
    words: jax.Array,  # u32[NP / 4]: the lane buffer as lane_words gives it
    bucket_offs: tuple[jax.Array, ...],
    bucket_sizes: tuple[jax.Array, ...],
    caps: tuple[int, ...],
    table_keys: jax.Array | None = None,  # u32[C,8] (or probe_pallas.pad_keys'
    table_vals: jax.Array | None = None,  # i32[C]    lane-dense i32[8,CP])
    table_cap: int = 0,
    depth: int = 0,
    digester: str = "sha256",
    pallas_probe: bool = False,
    probe_interpret: bool = False,
):
    """-> (tuple of u32[M_i, 8] digest states, i32[sum M_i] probe or None).

    A class's offsets and sizes are i32[M_i], one batch, or i32[T, R]
    with T * R == M_i: T row tiles of R rows, gathered and digested one
    after the other by one loop body (its temporaries are one tile's),
    the states in row order.

    Digest states are u32 words in the digester's natural order (big-
    endian words for sha256, little-endian for blake3); chunk-dict keys
    must be built with the same convention.
    """
    unroll = jax.default_backend() != "cpu"

    def digest_rows(cap, offs, sizes):
        if digester == "blake3":
            from nydus_snapshotter_tpu.ops import blake3_jax

            with jax.named_scope(f"gather_c{cap}"):
                blocks = _gather_pack_b3(words, offs, sizes, cap)
            with jax.named_scope(f"blake3_c{cap}"):
                return blake3_jax._blake3_batch_jit(blocks, sizes, unroll)
        return _gather_digest_sha(words, offs, sizes, cap, unroll)

    states = []
    for offs, sizes, cap in zip(bucket_offs, bucket_sizes, caps):
        if offs.ndim == 1:
            states.append(digest_rows(cap, offs, sizes))
        else:
            tiled = jax.lax.map(lambda tile: digest_rows(cap, *tile), (offs, sizes))
            states.append(tiled.reshape(-1, tiled.shape[-1]))
    probe = None
    if table_keys is not None:
        allq = jnp.concatenate(states, axis=0)
        with jax.named_scope("probe"):
            if pallas_probe:
                # DMA-pipelined Pallas probe (ops/probe_pallas): the XLA
                # gather formulation runs effectively element-serially on
                # TPU — at full-batch chunk counts it would dominate the
                # dispatch. Keys arrive in the wrap-free lane-dense layout.
                from nydus_snapshotter_tpu.ops import probe_pallas

                probe = probe_pallas.probe_padded(
                    table_keys, table_vals, allq, table_cap, depth,
                    interpret=probe_interpret,
                )
            else:
                from nydus_snapshotter_tpu.parallel.sharded_dict import _probe_local

                probe = _probe_local(table_keys, table_vals, allq, table_cap, depth)
    return tuple(states), probe


# ---------------------------------------------------------------------------
# Host orchestration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Extents:
    """A batch whose streams already lie in ONE buffer: ``table`` holds
    their (offset, length) in ``data``, ascending and disjoint (an
    in-memory layer tar and its members' data). process_many then takes
    the buffer as the lane's and the table as it is, and builds no second
    buffer: what lies between two files (a tar header, padding) is hashed
    by pass 1 like any byte and its candidates fall to no file in
    resolve(), whose seam argument does not ask what precedes a file.

    ``runs``: the batch is part of a layer that no one lane buffer holds
    (plan_batches). Its lane buffer is then these (start, length)
    stretches of ``data``, ascending, disjoint and whole words, back to
    back: uploaded as views and joined on the device, no host copy. Every
    extent of ``table`` (still in ``data``'s offsets) lies inside one of
    them. ``part``: (which batch of its layer this is, from 1; of how
    many), for the ``pack:lane.layout`` span."""

    data: "bytes | bytearray | np.ndarray"  # 1-D uint8 where an array
    table: list[tuple[int, int]]
    runs: "tuple[tuple[int, int], ...] | None" = None
    part: tuple[int, int] = (1, 1)


@dataclass(frozen=True)
class Batch:
    """One device batch of a layer that no one lane buffer holds: the
    ``files`` (indices into the layer's extent table, ascending) and the
    ``runs`` of the layer's buffer that hold them, as Extents.runs."""

    files: tuple[int, ...]
    runs: tuple[tuple[int, int], ...]

    @property
    def size(self) -> int:
        return sum(length for _start, length in self.runs)


def plan_batches(table: list[tuple[int, int]], size: int, max_size: int) -> list[Batch]:
    """A ``size``-byte buffer whose files are ``table`` (ascending,
    disjoint), as batches of WHOLE files that each pad below ADDRESS_LIMIT,
    so that resolve(), the cut state and a file's CDC are what they are in
    one batch and no chunk straddles two.

    The batches partition the buffer: file i brings the stretch from its
    first byte to the next file's (the first from 0, the last to ``size``),
    so every tar header, padding and end-of-archive block is uploaded in
    exactly one batch, and a seam lies where a file starts: a judged
    candidate sits >= 31 bytes inside its file (resolve), whatever
    precedes it in the batch's buffer.

    Which files share a batch does not follow their order in the buffer
    more than it must: a file of at least a quarter of ADDRESS_LIMIT is a
    batch of its own, and all other files fill batches in the buffer's
    order up to the limit, those batches first. _pass2 is keyed by each
    class's row count, and the benchmark's seeds (any two builds of one
    image) shuffle the members: a greedy split by position gives each
    order its own pair of programs, this rule gives the pip install
    tensorflow layer {libtensorflow_cc.so.2} and {the other ~30k files}
    whatever the order. The many-file batches go first because their
    host resolve then runs under the next batch's pass 1
    (process_batches); a one-file batch has nothing to hide.

    Raises FusedOverflow for a file whose own stretch pads past the limit
    (the cut state would have to cross batches) and for a seam off a word
    boundary (pass 2 reads words; no tar has one: members start at
    multiples of 512)."""
    bounds = [0, *(off for off, _length in table[1:]), size]
    own_from = ADDRESS_LIMIT // 4
    filled: list[Batch] = []
    own: list[Batch] = []
    files: list[int] = []
    runs: list[tuple[int, int]] = []
    total = 0
    for i, (_off, length) in enumerate(table):
        start, stretch = bounds[i], bounds[i + 1] - bounds[i]
        if length >= own_from:
            own.append(Batch((i,), ((start, stretch),)))
            continue
        if files and not lane_fits(total + stretch, max_size):
            filled.append(Batch(tuple(files), tuple(runs)))
            files, runs, total = [], [], 0
        if runs and sum(runs[-1]) == start:
            runs[-1] = (runs[-1][0], runs[-1][1] + stretch)
        else:
            runs.append((start, stretch))
        files.append(i)
        total += stretch
    if files:
        filled.append(Batch(tuple(files), tuple(runs)))
    batches = filled + own
    for batch in batches:
        if not lane_fits(batch.size, max_size):
            largest = max(table[i][1] for i in batch.files)
            raise FusedOverflow(
                f"one file of {largest} bytes (a stretch of {batch.size}) pads past int32 "
                "device addressing on its own; a batch is whole files"
            )
        if any(start % 4 or length % 4 for start, length in batch.runs):
            raise FusedOverflow(f"a batch's runs {batch.runs} do not start and end on a word")
    return batches


@functools.partial(jax.jit, static_argnames=("length",))
def _join(pieces: tuple[jax.Array, ...], length: int) -> jax.Array:
    """The lane buffer of a batch of runs, made on the device: the pieces
    back to back, zeros up to ``length``. One program per set of run
    lengths, and the runs' lengths follow the members' order in the tar,
    so it has to compile in no time: as slices written into zeros the
    chip's compiler takes 0.07-0.09 s for two runs of 0.5 GiB, as one
    concatenate of u8 1.1-1.4 s, which is also past the 1 s from which JAX
    writes a program to its persistent cache and counts a miss (compiled
    for a described v5e, PR 36)."""
    out, at = jnp.zeros(length, pieces[0].dtype), 0
    for piece in pieces:
        out = jax.lax.dynamic_update_slice(out, piece, (at,))
        at += piece.shape[0]
    return out


def _upload(pieces: list[np.ndarray], length: int) -> tuple[jax.Array, int]:
    """Host arrays -> (the device array of ``length`` they make back to
    back, nothing waited for; the _join programs that compiled, 0 or 1).
    A lane buffer that is one host array goes up as it stands; a batch of
    runs as views of the layer's buffer, joined and zero-padded on the
    device (_join: one small program per set of run lengths, compiled on
    first use and kept by the process: no compile counter sees it, so
    the caller's span says so; by the jit cache's size before and after,
    so of two packs at once either may count the other's)."""
    if len(pieces) == 1 and pieces[0].shape[0] == length:
        return jnp.asarray(pieces[0]), 0
    had = _join._cache_size()
    joined = _join(tuple(jnp.asarray(p) for p in pieces), length)
    return joined, _join._cache_size() - had


def _u8(data) -> np.ndarray:
    """bytes, bytearray or a u8 array, as the u8 array (a view, no copy)."""
    return np.frombuffer(data, dtype=np.uint8) if isinstance(data, (bytes, bytearray)) else data


def _checked_table(streams: Extents, size: int) -> list[tuple[int, int]]:
    """An Extents' table as ints, every extent inside its ``size``-byte
    buffer: the device clamps a gather that leaves the buffer, so one that
    does is refused here."""
    table = [(int(off), int(length)) for off, length in streams.table]
    if any(off < 0 or length < 0 or off + length > size for off, length in table):
        raise ValueError(f"an extent lies outside its {size}-byte buffer")
    return table


@dataclass
class Begun:
    """A batch whose upload and pass 1 are enqueued and whose candidate
    counts nobody has waited for: FusedDeviceEngine.begin's result and
    process_many's ``begun``. Whoever began it closes it; a begun batch
    that no process_many finishes was counted nowhere."""

    data: object  # what it was begun on: process_many's Extents hold this very object
    table: "list[tuple[int, int]] | None"  # None: a bare buffer, the extents come with process_many
    n: int  # valid bytes of the buffer
    before: dict[str, float]  # the Stages' seconds at begin: the batch's own are what it adds
    # the host arrays that went up (the lane buffer, or a batch's runs of
    # it), alive and unwritten until the upload is done (None: a batch
    # without a byte, nothing enqueued)
    buf: "list[np.ndarray] | None" = None
    buffer_dev: "jax.Array | None" = None  # u8[NP], pass 1's operand: None once its candidates are on the host
    words_dev: "jax.Array | None" = None  # u32[NP / 4], the same bytes as pass 2 gathers them (lane_words)
    words: tuple = ()  # _pass1's six outputs, still on the device
    wcap_s: int = 0
    wcap_l: int = 0
    t0: float = 0.0  # perf_counter at the enqueue's start
    # seconds since t0 that the host spent on other batches of the same
    # layer (process_batches): cover for this one's upload and pass 1
    sibling_s: float = 0.0
    # begun by the caller ahead of host work of its own (False: by
    # process_batches itself, after the caller's scan: no early start)
    early: bool = True

    def drop_bytes(self) -> None:
        """Free pass 1's u8 operand: pass 2 reads the words, and the two
        are a buffer's length of HBM each."""
        if self.buffer_dev is not None:
            self.buffer_dev.delete()
        self.buffer_dev = None

    def close(self) -> None:
        """Free the device arrays now, in flight or not (the runtime lets
        work that reads them finish: 1.6 ms for the call on the v5e, my
        chip run, PR 32); nothing waits."""
        self.drop_bytes()
        if self.words_dev is not None:
            for a in (self.words_dev, *self.words):
                a.delete()
        self.buf = self.words_dev = None
        self.words = ()


@dataclass(frozen=True)
class FusedResult:
    """Per-stream chunk extents/digests + optional dict-probe hits."""

    cuts: list[np.ndarray]  # per-stream exclusive cut ends
    digests: list[list[bytes]]  # per-stream raw 32-B sha256 digests
    probe: np.ndarray | None  # i32 over all chunks in stream order (0=miss)


class FusedDeviceEngine:
    """Full-path device convert for a batch of per-file streams.

    Mirrors ChunkDigestEngine.process_many semantics (per-file CDC with
    the engine's CDCParams, per-chunk sha256) but runs the whole batch as
    two device dispatches. ``chunk_dict`` (keys u32[C,8] / values i32[C],
    the sharded-dict single-shard layout) adds the dedup probe to pass 2.
    """

    def __init__(
        self,
        chunk_size: int = 0x100000,
        digester: str = "sha256",
    ):
        if digester not in ("sha256", "blake3"):
            raise ValueError(f"unknown digester {digester!r}")
        self.params = cdc.CDCParams(chunk_size)
        self.digester = digester

    def _blocks_of(self, size: int) -> int:
        """Digest-layout capacity units of one chunk (SHA 64-B blocks or
        blake3 leaves) — the bucket-class axis."""
        if self.digester == "blake3":
            from nydus_snapshotter_tpu.ops import blake3_jax

            return blake3_jax.n_leaves(size)
        return sha256.n_padded_blocks(size)

    def max_read_span(self) -> int:
        """Largest pass-2 gather span any bucket can issue, in bytes —
        the guard padded_length() leaves for, and the shard halo
        ops/mesh_pack must append to every per-device slab so a chunk
        cut at a shard boundary still gathers without clamping."""
        # + a word: a gather reads whole words, from the one that holds the
        # chunk's first byte to one past its capacity (_chunk_words)
        return self._blocks_of(self.params.max_size) * self._unit_bytes() + 4

    def _unit_bytes(self) -> int:
        """Bytes a row gathers per unit of its class's capacity: a SHA
        block's or a blake3 leaf's."""
        if self.digester == "blake3":
            from nydus_snapshotter_tpu.ops import blake3_jax

            return blake3_jax.LEAF_BYTES
        return 64

    # -- planning ------------------------------------------------------------

    def layout(self, arrs: list[np.ndarray]) -> tuple[np.ndarray, list[tuple[int, int]]]:
        """Concatenate streams; returns (buffer, [(offset, length)])."""
        table = []
        total = 0
        for a in arrs:
            table.append((total, a.size))
            total += a.size
        npad = padded_length(total, self.params.max_size)
        buf = np.zeros(npad, dtype=np.uint8)
        pos = 0
        for a in arrs:
            buf[pos : pos + a.size] = a
            pos += a.size
        return buf, table

    def resolve(
        self,
        cand_s: np.ndarray,
        cand_l: np.ndarray,
        table: list[tuple[int, int]],
    ) -> list[np.ndarray]:
        """Per-file cut resolution over the global candidate arrays.

        Candidates judged per file always sit >= min_size-1 >= 31 bytes
        past the file start, where the 32-byte gear window lies entirely
        inside the file — so global (concatenated) hashing resolves to
        bit-identical per-file cuts (the ops/chunker seam argument).
        """
        cuts = []
        for off, length in table:
            if length == 0:
                cuts.append(np.asarray([], dtype=np.int64))
                continue
            lo_s, hi_s = np.searchsorted(cand_s, [off, off + length])
            lo_l, hi_l = np.searchsorted(cand_l, [off, off + length])
            cuts.append(
                cdc.resolve_cuts(
                    cand_s[lo_s:hi_s] - off,
                    cand_l[lo_l:hi_l] - off,
                    length,
                    self.params,
                )
            )
        return cuts

    def plan_buckets(
        self, table: list[tuple[int, int]], cuts: list[np.ndarray]
    ) -> tuple[list[Bucket], list[tuple[int, int]]]:
        """Bucket chunks by pow2 padded-block class with EXACT counts.

        Returns (buckets, flat chunk order) where the flat order is
        (bucket, row) assignments per chunk in stream order, used to
        scatter results back.
        """
        max_blocks = self._blocks_of(self.params.max_size)
        per_class: dict[int, list[tuple[int, int]]] = {}
        real_blocks: dict[int, int] = {}
        order: list[tuple[int, int]] = []
        for (f_off, _f_len), f_cuts in zip(table, cuts):
            prev = 0
            for cut in f_cuts:
                size = int(cut) - prev
                nb = self._blocks_of(size)
                cap = min(_pow2_ceil(nb), max_blocks)
                rows = per_class.setdefault(cap, [])
                real_blocks[cap] = real_blocks.get(cap, 0) + nb
                order.append((cap, len(rows)))
                rows.append((f_off + prev, size))
                prev = int(cut)
        buckets = []
        unit_bytes = self._unit_bytes()
        for cap in sorted(per_class):
            rows = per_class[cap]
            m, tile = class_rows(len(rows), cap * unit_bytes)
            offs = np.zeros(m, dtype=np.int32)
            sizes = np.zeros(m, dtype=np.int32)
            offs[: len(rows)] = [r[0] for r in rows]
            sizes[: len(rows)] = [r[1] for r in rows]
            buckets.append(Bucket(cap, offs, sizes, len(rows), real_blocks[cap], tile))
        return buckets, order

    # -- execution -----------------------------------------------------------

    def enqueue_pass1(self, buffer_dev: jax.Array, n: int):
        """Pass 1 called on an already-device-resident buffer and nothing
        waited for -> (its six outputs, still on the device; wcap_s;
        wcap_l). A first call of a new shape compiles here."""
        p = self.params
        wcap_s = _wcap_for(n, p.bits + 2)
        wcap_l = _wcap_for(n, p.bits - 2)
        words = _pass1(
            buffer_dev, jnp.int32(n), p.mask_small, p.mask_large, wcap_s, wcap_l
        )
        return words, wcap_s, wcap_l

    @staticmethod
    def word_counts(words, wcap_s: int, wcap_l: int) -> tuple[int, int]:
        """Pass 1's first sync: the candidate-word counts on the host."""
        nw_s, nw_l = int(words[2]), int(words[5])
        if nw_s > wcap_s or nw_l > wcap_l:
            raise FusedOverflow(
                f"candidate words {nw_s}/{nw_l} exceed caps {wcap_s}/{wcap_l}"
            )
        return nw_s, nw_l

    @staticmethod
    def candidate_positions(sel, got, nw: int, n: int) -> np.ndarray:
        """Candidate D2H: word indices + bitmap words -> int64 byte positions."""
        sel = np.asarray(jax.device_get(sel))[:nw].astype(np.int64)
        got = np.asarray(jax.device_get(got))[:nw]
        bits = np.unpackbits(
            got.view(np.uint8).reshape(-1, 4), axis=1, bitorder="little"
        )  # [nw, 32]
        widx, bit = np.nonzero(bits)
        pos = sel[widx] * 32 + bit
        return pos[pos < n]

    def candidates(self, buffer_dev: jax.Array, n: int) -> tuple[np.ndarray, np.ndarray]:
        """Pass 1 + candidate D2H on an already-device-resident buffer."""
        words, wcap_s, wcap_l = self.enqueue_pass1(buffer_dev, n)
        nw_s, nw_l = self.word_counts(words, wcap_s, wcap_l)
        sel_s, got_s, _, sel_l, got_l, _ = words
        return (
            self.candidate_positions(sel_s, got_s, nw_s, n),
            self.candidate_positions(sel_l, got_l, nw_l, n),
        )

    def digest_probe(
        self,
        words_dev: jax.Array,  # u32: the buffer as lane_words gives it
        buckets: list[Bucket],
        chunk_dict: tuple[np.ndarray, np.ndarray] | None = None,
        depth: int = 8,
        probe_kernel: str = "auto",  # "auto" | "xla" | "pallas" | "pallas-interpret"
        dict_epoch: int | None = None,
    ):
        """Pass 2: per-bucket digest states + optional dict probe.

        ``probe_kernel``: auto = the DMA-pipelined Pallas probe on real
        TPU, the XLA gather elsewhere; "pallas-interpret" forces the
        Pallas lowering in interpret mode (CPU differential tests).

        ``dict_epoch``: the dict's mutation epoch (ShardedChunkDict
        ``fused_probe_tables``). Incremental inserts mutate the table
        arrays IN PLACE, so the staged-table cache must key on the epoch
        — identity alone would keep serving the pre-insert device copy.
        """
        # a tiled class goes as [tiles, tile_rows]: the shape tells _pass2
        shapes = [(b.tiles, -1) if b.tiles > 1 else (-1,) for b in buckets]
        offs = tuple(jnp.asarray(b.offsets.reshape(s)) for b, s in zip(buckets, shapes))
        sizes = tuple(jnp.asarray(b.sizes.reshape(s)) for b, s in zip(buckets, shapes))
        caps = tuple(b.cap_blocks for b in buckets)
        tk = tv = None
        table_cap = 0
        use_pallas = probe_interpret = False
        if chunk_dict is not None:
            from nydus_snapshotter_tpu.ops import probe_pallas

            if probe_kernel not in ("auto", "xla", "pallas", "pallas-interpret"):
                raise ValueError(f"unknown probe kernel {probe_kernel!r}")
            keys, vals = chunk_dict
            table_cap = keys.shape[0]
            if probe_kernel == "auto":
                use_pallas = probe_pallas.supported()
            elif probe_kernel != "xla":
                use_pallas = True
                probe_interpret = probe_kernel == "pallas-interpret"
            if use_pallas:
                tk, tv = self._padded_tables(keys, vals, depth, dict_epoch)
            else:
                tk, tv = jnp.asarray(keys), jnp.asarray(vals)
            # the branch really taken (what "auto" resolved to)
            self.probe_kernel_used = "pallas" if use_pallas else "xla"
        states, probe = _pass2(
            words_dev, offs, sizes, caps, tk, tv, table_cap, depth,
            digester=self.digester, pallas_probe=use_pallas,
            probe_interpret=probe_interpret,
        )
        return states, probe

    def _padded_tables(
        self,
        keys: np.ndarray,
        vals: np.ndarray,
        depth: int,
        dict_epoch: int | None = None,
    ):
        """Wrap-free padded device keys (and the values beside them) for
        the Pallas probe, cached per
        (dict identity, depth, epoch) — padding copies tens of MB for
        million-entry dicts and repeated digest_probe calls (the bench
        loop) must not pay it, or the H2D re-upload, per dispatch. The
        epoch term invalidates staged copies when incremental inserts
        mutate the arrays in place (same identity, new contents)."""
        from nydus_snapshotter_tpu.ops import probe_pallas

        cached = getattr(self, "_table_cache", None)
        if (
            cached is not None
            and cached[0] is keys  # identity: the cache keeps them alive,
            and cached[1] is vals  # so `is` cannot alias freed objects
            and cached[2] == depth
            and cached[3] == dict_epoch
        ):
            return cached[4], cached[5]
        tk, tv = jnp.asarray(probe_pallas.pad_keys(keys, depth)), jnp.asarray(vals)
        self._table_cache = (keys, vals, depth, dict_epoch, tk, tv)
        return tk, tv

    def _digest_bytes(self, state_row: np.ndarray) -> bytes:
        if self.digester == "blake3":
            from nydus_snapshotter_tpu.ops import blake3_jax

            return blake3_jax.digest_to_bytes(state_row)
        return sha256.digest_to_bytes(state_row)

    def _lay(self, streams):
        """The layout stage -> (the host arrays that make the lane buffer
        back to back, or None for a batch without a byte; its
        padded_length; its [(offset, length)] table in the buffer's own
        offsets, or None for a bare buffer, whose extents come later; the
        valid bytes; the bytes copied to build it)."""
        if isinstance(streams, Extents) and streams.runs is not None:
            return self._lay_runs(streams)
        if isinstance(streams, (Extents, bytes, bytearray, np.ndarray)):
            arr = _u8(streams.data if isinstance(streams, Extents) else streams)
            if isinstance(streams, Extents):
                table = _checked_table(streams, arr.size)
                empty = not any(length for _off, length in table)
            else:
                table, empty = None, not arr.size
            if empty:
                return None, 0, table, 0, 0
            buf, copied = lane_buffer(arr, padded_length(arr.size, self.params.max_size))
            return [buf], buf.size, table, arr.size, copied
        arrs = [_u8(s) for s in streams]
        n = sum(a.size for a in arrs)
        if n == 0:
            return None, 0, [(0, 0)] * len(arrs), 0, 0
        buf, table = self.layout(arrs)
        return [buf], buf.size, table, n, n

    def _lay_runs(self, streams: Extents):
        """_lay for a batch of runs: the runs as views of the layer's
        buffer, the table moved from the layer's offsets to the batch's."""
        arr = _u8(streams.data)
        runs = [(int(start), int(length)) for start, length in streams.runs]
        ends = [start + length for start, length in runs]
        if any(
            start < 0 or length < 0 or start % 4 or length % 4 for start, length in runs
        ) or any(end > nxt for end, (nxt, _l) in zip(ends, runs[1:] + [(arr.size, 0)])):
            raise ValueError(f"runs {runs} are not ascending disjoint whole words of a {arr.size}-byte buffer")
        starts = [start for start, _length in runs]
        bases = [0, *itertools.accumulate(length for _start, length in runs)]
        table = []
        for off, length in _checked_table(streams, arr.size):
            j = max(bisect.bisect_right(starts, off) - 1, 0)
            if not starts[j] <= off <= off + length <= ends[j]:
                raise ValueError(f"the extent ({off}, {length}) lies in no run of its batch")
            table.append((off - starts[j] + bases[j], length))
        n = bases[-1]
        if not any(length for _off, length in table):
            return None, 0, table, 0, 0
        pieces = [arr[start:end] for start, end in zip(starts, ends) if end > start]
        return pieces, padded_length(n, self.params.max_size), table, n, 0

    def begin(self, streams, stages) -> "Begun":
        """The lane's first half: the buffer made ready, its upload and
        pass 1 ENQUEUED, nothing waited for. Neither needs a file table,
        so a caller that has the layer in memory begins here, does the
        host work that needs nothing from the device (parse a dictionary,
        walk the tar's members), and hands the result to process_many as
        ``begun`` with the extents it found meanwhile.

        ``streams``: what process_many takes, or the bare buffer (bytes,
        bytearray, u8 array) that the Extents will be over. ``stages``: the
        running ``trace.Stages`` to open ``pack:lane.layout``, ``.h2d`` and
        the first ``.pass1`` on; the last is left running."""
        from nydus_snapshotter_tpu import failpoint

        # Device batch boundary: chaos-testable (an injected error
        # propagates — callers redo a batch on the host lanes only for
        # FusedOverflow, and count it).
        failpoint.hit("fused.dispatch")
        stages.next("pack:lane.layout")
        # it sums by name over all it ran; taken with the caller's stage closed
        # (a batch begun after the scan: the scan is no cover for it)
        before = dict(stages.seconds)
        pieces, npad, table, n, copied = self._lay(streams)
        data = streams.data if isinstance(streams, Extents) else streams
        if pieces is None:
            return Begun(data, table, 0, before)
        batch, batches = streams.part if isinstance(streams, Extents) else (1, 1)
        stages.annotate(
            bytes=n, padded_bytes=npad, copied_bytes=copied,
            batch=batch, batches=batches, runs=len(pieces),
        )
        # committed to the default device. From here to word_counts these
        # two leaves are the host's side of asynchronous device work: the
        # call that enqueues it, not the work.
        t0 = stages.next("pack:lane.h2d", bytes=sum(p.size for p in pieces)).t0
        buffer_dev, joins = _upload(pieces, npad)
        stages.annotate(join_compiles=joins)
        # a first call of a new buffer length compiles here
        stages.next("pack:lane.pass1")
        words, wcap_s, wcap_l = self.enqueue_pass1(buffer_dev, n)
        # the same bytes once more, as the words pass 2 gathers from: enqueued
        # behind the bytes and the call, so the upload runs under _pass1 and
        # nothing waits for it before pass 2 is dispatched
        words_dev, joins = _upload([lane_words(p) for p in pieces], npad // 4)
        stages.annotate(wcap_s=wcap_s, wcap_l=wcap_l, join_compiles=joins)
        return Begun(
            data, table, n, before, pieces, buffer_dev, words_dev, words, wcap_s, wcap_l, t0
        )

    def process_many(
        self,
        streams: "list[bytes | np.ndarray] | Extents",
        chunk_dict: tuple[np.ndarray, np.ndarray] | None = None,
        depth: int = 8,
        probe_kernel: str = "auto",
        dict_epoch: int | None = None,
        stages=None,
        begun: "Begun | None" = None,
    ) -> FusedResult:
        """``streams``: separate byte strings, which layout() copies back to
        back into a fresh buffer, or an Extents: streams that already lie
        in one buffer, which is then the lane's own (lane_buffer). Same
        lane from the upload on, same result for the same streams.

        ``stages``: the caller's running ``trace.Stages`` (a pack's) to
        drive ``pack:lane.*`` on, the last one closed; its own if None.

        ``begun``: begin()'s result for the very buffer ``streams`` is an
        Extents over, on the same ``stages`` (the caller's to close()).
        Without one the batch is begun here: the same code, in the old
        order."""
        from nydus_snapshotter_tpu import trace

        # One span a stage, consecutive (trace.Stages): a span's enter/exit
        # is the only clock read at its boundary, and the stage counters
        # and the caller's stats are fed from the spans' own seconds.
        with (trace.Stages() if stages is None else stages) as lane:
            early = begun is not None and begun.early
            if begun is None:
                begun = self.begin(streams, lane)
            elif not (isinstance(streams, Extents) and streams.data is begun.data):
                raise ValueError("the lane was begun on another buffer than these extents lie in")
            # begun on the bare buffer, the extents come now; begun on
            # Extents (a batch of runs), the table is the batch's already
            table = begun.table if begun.table is not None else _checked_table(streams, begun.n)
            n = begun.n
            if begun.words_dev is None or not any(length for _off, length in table):
                return FusedResult(
                    cuts=[np.asarray([], dtype=np.int64) for _ in table],
                    digests=[[] for _ in table],
                    probe=np.zeros(0, np.int32) if chunk_dict is not None else None,
                )
            # the wait for what begin enqueued. covered_s: what of window_s
            # (the enqueue's start to the counts on the host) the caller
            # spent in stages of its own, not waiting in the lane's, or on
            # another batch of the same layer (sibling_s)
            lane.next("pack:lane.pass1")
            nw_s, nw_l = self.word_counts(begun.words, begun.wcap_s, begun.wcap_l)
            lane.annotate(
                words_s=nw_s,
                words_l=nw_l,
                window_s=perf_counter() - begun.t0,
                covered_s=begun.sibling_s
                + sum(
                    s - begun.before.get(name, 0.0)
                    for name, s in lane.seconds.items()
                    if not name.startswith("pack:lane.")
                ),
            )
            sel_s, got_s, _, sel_l, got_l, _ = begun.words
            lane.next("pack:lane.cand_d2h")
            cand_s = self.candidate_positions(sel_s, got_s, nw_s, n)
            cand_l = self.candidate_positions(sel_l, got_l, nw_l, n)
            lane.annotate(candidates_s=len(cand_s), candidates_l=len(cand_l))
            begun.drop_bytes()
            lane.next("pack:lane.resolve", files=len(table))
            cuts = self.resolve(cand_s, cand_l, table)
            # a file no longer than min_size is one chunk, no candidate judged
            # (an empty one has no chunk)
            min_size = self.params.min_size
            lane.annotate(
                chunks=sum(len(c) for c in cuts),
                single_chunk_files=sum(1 for _off, length in table if 0 < length <= min_size),
            )
            lane.next("pack:lane.plan")
            buckets, order = self.plan_buckets(table, cuts)
            # rows the floor added to each class, beyond its power of two
            floored = [max(0, len(b.offsets) - _pow2_ceil(b.count)) for b in buckets]
            lane.annotate(
                classes=[[b.cap_blocks, b.count, len(b.offsets)] for b in buckets],
                blocks_real=sum(b.blocks for b in buckets),
                blocks_padded=sum(len(b.offsets) * b.cap_blocks for b in buckets),
                row_floor_classes=sum(1 for r in floored if r),
                row_floor_rows=sum(floored),
                blocks_tiled=sum(len(b.offsets) * b.cap_blocks for b in buckets if b.tiles > 1),
                row_tiles=sum(b.tiles - 1 for b in buckets),
                batch_mib_max=max(len(b.offsets) // b.tiles * b.cap_blocks for b in buckets)
                * self._unit_bytes()
                / 2**20,
            )
            # a first call of a new plan compiles here: programs_after tells
            lane.next("pack:lane.pass2", programs_before=_pass2._cache_size())
            states, probe = self.digest_probe(
                begun.words_dev, buckets, chunk_dict, depth, probe_kernel, dict_epoch
            )
            jax.block_until_ready(states)
            lane.annotate(programs_after=_pass2._cache_size())
            lane.next("pack:lane.digest_d2h", chunks=len(order))
            by_cap = {
                b.cap_blocks: np.asarray(jax.device_get(s))
                for b, s in zip(buckets, states)
            }
            flat_digests = [
                self._digest_bytes(by_cap[cap][row]) for cap, row in order
            ]
            probe_np = None
            if probe is not None:
                # probe ran over the concatenation of bucket rows (incl.
                # padding); remap to stream order via each bucket's row base
                probe_all = np.asarray(jax.device_get(probe))
                base = {}
                acc = 0
                for b in buckets:
                    base[b.cap_blocks] = acc
                    acc += len(b.offsets)
                probe_np = np.asarray(
                    [probe_all[base[cap] + row] for cap, row in order], dtype=np.int32
                )
            out_digests: list[list[bytes]] = []
            pos = 0
            for f_cuts in cuts:
                out_digests.append(flat_digests[pos : pos + len(f_cuts)])
                pos += len(f_cuts)
        took = {name: s - begun.before.get(name, 0.0) for name, s in lane.seconds.items()}
        _record_dispatch(
            n,
            {
                "layout": took["pack:lane.layout"],
                "h2d": took["pack:lane.h2d"],
                "pass1_gear": took["pack:lane.pass1"] + took["pack:lane.cand_d2h"],
                "host_resolve": took["pack:lane.resolve"] + took["pack:lane.plan"],
                "pass2_digest": took["pack:lane.pass2"],
                "digest_d2h": took["pack:lane.digest_d2h"],
            },
            early_start=early,
        )
        return FusedResult(cuts=cuts, digests=out_digests, probe=probe_np)

    def process_batches(
        self,
        streams: Extents,
        chunk_dict: tuple[np.ndarray, np.ndarray] | None = None,
        depth: int = 8,
        probe_kernel: str = "auto",
        dict_epoch: int | None = None,
        stages=None,
        begun: "Begun | None" = None,
    ) -> FusedResult:
        """process_many for a layer of any size: where no one lane buffer
        holds ``streams.data``, its files go as batches of whole files
        (plan_batches), each through begin and process_many on runs of the
        same host buffer, and the result is one, in the table's order. A
        layer that fits is process_many's own one batch, ``begun`` by the
        caller or not (what was begun whole fits).

        Batch k+1 is begun (its upload and pass 1 enqueued) before batch
        k's candidates are waited for, so the device works on it while the
        host resolves and plans k; at most two batches are on the device.
        Each batch is a dispatch of the counters, fed with the leaves that
        ran since the one before it, and the layer counts once in
        ntpu_fused_convert_split_packs_total."""
        from nydus_snapshotter_tpu import trace

        data, max_size = streams.data, self.params.max_size
        size = _u8(data).size
        if begun is not None or lane_fits(size, max_size):
            return self.process_many(
                streams, chunk_dict, depth, probe_kernel, dict_epoch, stages, begun
            )
        table = _checked_table(streams, size)
        plan = plan_batches(table, size, max_size)
        parts = [
            Extents(data, [table[i] for i in batch.files], batch.runs, (k + 1, len(plan)))
            for k, batch in enumerate(plan)
        ]
        started: list[Begun] = []
        results: list[FusedResult] = []
        with (trace.Stages() if stages is None else stages) as lane:
            try:
                for k, part in enumerate(parts):
                    for nxt in parts[len(started) : k + 2]:
                        started.append(self.begin(nxt, lane))
                        started[-1].early = False
                    t0 = perf_counter()
                    results.append(
                        self.process_many(
                            part, chunk_dict, depth, probe_kernel, dict_epoch, lane, started[k]
                        )
                    )
                    started[k].close()
                    if k + 1 < len(parts):
                        # what the next batch adds to the counters starts here, and
                        # this batch's lane leaves were cover for its upload and pass 1
                        started[k + 1].before = dict(lane.seconds)
                        started[k + 1].sibling_s = perf_counter() - t0
            finally:
                for b in started:
                    b.close()
        if parts:
            _split_packs_counter().inc()
        cuts: list = [None] * len(table)
        digests: list = [None] * len(table)
        probes: list = [None] * len(table)
        for batch, res in zip(plan, results):
            pos = 0
            for i, f_cuts, f_digests in zip(batch.files, res.cuts, res.digests):
                cuts[i], digests[i] = f_cuts, f_digests
                if res.probe is not None:
                    probes[i] = res.probe[pos : pos + len(f_cuts)]
                    pos += len(f_cuts)
        probe = None
        if chunk_dict is not None:
            probe = np.concatenate([np.zeros(0, np.int32), *probes]).astype(np.int32)
        return FusedResult(cuts=cuts, digests=digests, probe=probe)
