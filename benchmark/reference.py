"""The plain reference: what a RAFS convert has to say about a file's bytes.

Imports nothing of the program and takes nothing it has made. Written from
the format's rules, one arm for each value a pack argument may take:

* cuts (``--chunking``): ``cdc`` is gear-v2 table ``G[b] = fmix32((b + 1) *
  0x9E3779B1)``, 32-bit gear hash ``h_i = (h_{i-1} << 1) + G[x_i]``, FastCDC
  with normalisation level 2 (min = avg/4, max = 4*avg, masks of bits+2 /
  bits-2 low bits), the hash held for ``WINDOW`` positions at a time; ``fixed``
  ends a chunk every chunk-size bytes from the file's start, the last chunk
  holds the remainder, an empty file has none;
* digests (``--digester``): ``sha256`` (``hashlib``); ``blake3``, written here
  in numpy from the BLAKE3 specification (O'Connor, Aumasson, Neves,
  Wilcox-O'Hearn, 2020), every chunk of a batch of files at once;
* stored bytes (``--compressor``): the lz4 block format, decoded here; a zstd
  frame (RFC 8878), decoded by ``zstandard`` and held to one whole frame that
  needs no dictionary.
"""

from __future__ import annotations

import hashlib

import numpy as np
import zstandard


def _gear_table() -> np.ndarray:
    x = np.arange(256, dtype=np.uint64)
    m = np.uint64(0xFFFFFFFF)
    x = ((x + np.uint64(1)) * np.uint64(0x9E3779B1)) & m
    x ^= x >> np.uint64(16)
    x = (x * np.uint64(0x85EBCA6B)) & m
    x ^= x >> np.uint64(13)
    x = (x * np.uint64(0xC2B2AE35)) & m
    x ^= x >> np.uint64(16)
    return x.astype(np.uint32)


GEAR = _gear_table()
WINDOW = 64 << 20  # positions of a file whose hashes are held at once (4 bytes each), not the whole file's
GEAR_BLOCK = 1 << 18  # positions hashed at once: the doubling's operands stay in the caches


def window_hashes(data: np.ndarray, start: int, stop: int) -> np.ndarray:
    """h at positions [start, stop) of ``data``, from the 31 bytes before
    ``start`` (none before the file's start) and ``data[start:stop]``. The
    hash forgets bytes older than 32 positions, so it is the sum of G over the
    window ending there, each shifted by its age. Built by doubling: a window
    of 2w is a window of w plus the window of w that ended w positions
    earlier, shifted by w; ``GEAR_BLOCK`` positions at a time."""
    out = np.empty(stop - start, np.uint32)
    h, shifted = np.empty(GEAR_BLOCK + 31, np.uint32), np.empty(GEAR_BLOCK + 31, np.uint32)
    for s in range(start, stop, GEAR_BLOCK):
        e = min(stop, s + GEAR_BLOCK)
        lead, m = min(s, 31), e - s + 31
        h[:31 - lead] = 0  # before the file's start the history is zero
        h[31 - lead:m] = GEAR[data[s - lead:e]]
        for w in (1, 2, 4, 8, 16):
            np.left_shift(h[:m - w], np.uint32(w), out=shifted[:m - w])
            h[w:m] += shifted[:m - w]
        out[s - start:e - start] = h[31:m]
    return out


def plain_cuts(data: np.ndarray, avg: int, chunking: str = "cdc") -> list[int]:
    """Chunk ends (exclusive) of one file: byte-sequential FastCDC, or
    fixed-size chunks of ``avg`` bytes."""
    n = len(data)
    if chunking == "fixed":
        return list(range(avg, n, avg)) + [n] * (n > 0)
    if chunking != "cdc":
        raise ValueError(f"no cut rule {chunking!r}")
    bits = avg.bit_length() - 1
    lo, hi = avg // 4, 4 * avg
    mask_s, mask_l = np.uint32((1 << (bits + 2)) - 1), np.uint32((1 << (bits - 2)) - 1)
    h, base = np.zeros(0, np.uint32), 0  # h[i - base] is the hash at position i
    cuts, start = [], 0
    while n - start > lo:
        if base + len(h) < min(start + hi, n):  # every candidate of this chunk lies below start + hi
            base, h = start, window_hashes(data, start, min(n, start + max(WINDOW, hi)))
        end = None
        a, b = start + lo - 1, min(start + avg - 1, n)  # candidate i: chunk ends at i + 1
        hit = np.flatnonzero((h[a - base:b - base] & mask_s) == 0)
        if hit.size:
            end = a + int(hit[0]) + 1
        else:
            a, b = start + avg - 1, min(start + hi - 1, n)
            hit = np.flatnonzero((h[a - base:b - base] & mask_l) == 0)
            if hit.size:
                end = a + int(hit[0]) + 1
        if end is None:
            end = start + hi if n - start > hi else n
        cuts.append(end)
        start = end
    if n > start:
        cuts.append(n)
    return cuts


# -- BLAKE3 -----------------------------------------------------------------------

B3_IV = np.array([0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
                  0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19], np.uint32)  # SHA-256's
B3_PERM = (2, 6, 3, 10, 7, 0, 4, 13, 1, 11, 12, 5, 9, 14, 15, 8)
CHUNK_START, CHUNK_END, PARENT, ROOT = 1, 2, 4, 8
B3_BLOCK, B3_CHUNK = 64, 1024
# bytes of pieces compressed together: one batch's operands stay a few MiB
B3_BATCH = 16 << 20


def _b3_schedule() -> np.ndarray:
    """Round r reads message word ``schedule[r][i]`` where round 0 reads word i."""
    rounds = [list(range(16))]
    for _ in range(6):
        rounds.append([rounds[-1][p] for p in B3_PERM])
    return np.array(rounds)


B3_SCHEDULE = _b3_schedule()


def _rotr(x: np.ndarray, n: int) -> np.ndarray:
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def _g(a, b, c, d, mx, my) -> None:
    """The quarter-round on four columns at once, in place: row i of a, b, c, d
    is state word i, 4 + i, 8 + i, 12 + i of every lane."""
    a += b
    a += mx
    d[:] = _rotr(d ^ a, 16)
    c += d
    b[:] = _rotr(b ^ c, 12)
    a += b
    a += my
    d[:] = _rotr(d ^ a, 8)
    c += d
    b[:] = _rotr(b ^ c, 7)


def b3_compress(cv: np.ndarray, m: np.ndarray, counter: np.ndarray, block_len: np.ndarray,
                flags: np.ndarray) -> np.ndarray:
    """The compression function over lanes: cv u32[8, L], m u32[16, L] (the
    block's words, little-endian), counter / block_len / flags [L] -> the
    first 8 words of the output, u32[8, L]: the chaining value, or with ROOT
    the hash."""
    lanes = cv.shape[1]
    a, b = cv[:4].copy(), cv[4:].copy()
    c = np.repeat(B3_IV[:4, None], lanes, axis=1)
    counter, d = np.asarray(counter, np.uint64), np.empty((4, lanes), np.uint32)
    d[0], d[1], d[2], d[3] = counter & np.uint64(0xFFFFFFFF), counter >> np.uint64(32), block_len, flags
    for s in B3_SCHEDULE:
        _g(a, b, c, d, m[s[0:8:2]], m[s[1:8:2]])  # columns
        b, c, d = np.roll(b, -1, 0), np.roll(c, -2, 0), np.roll(d, -3, 0)
        _g(a, b, c, d, m[s[8:16:2]], m[s[9:16:2]])  # diagonals: (0, 5, 10, 15), (1, 6, 11, 12), ...
        b, c, d = np.roll(b, 1, 0), np.roll(c, 2, 0), np.roll(d, 3, 0)
    return np.concatenate([a ^ c, b ^ d])


def _b3_batch(pieces: list) -> list[bytes]:
    """BLAKE3 of each piece. Every 1,024-byte chunk of every piece is a lane:
    the pieces' inner chunks (all full) first, then their last chunks by
    descending count of blocks, so that the lanes still at block k are a
    prefix. Then each piece's chunk chaining values are merged in pairs,
    level by level, the odd one carried up: the left subtree holds the
    largest power of two of chunks less than the total. A piece of one chunk
    has its hash from the chunk's last block, flagged ROOT."""
    sizes = np.array([len(p) for p in pieces], np.int64)
    chunks = np.maximum(1, -(-sizes // B3_CHUNK))
    tail_len = sizes - (chunks - 1) * B3_CHUNK  # 0 only for an empty piece
    tail_blocks = np.maximum(1, -(-tail_len // B3_BLOCK))
    n_inner = int((chunks - 1).sum())
    inner_at = np.concatenate([[0], np.cumsum(chunks - 1)])
    by_blocks = np.argsort(-tail_blocks, kind="stable")
    tail_row = np.empty(len(pieces), np.int64)
    tail_row[by_blocks] = n_inner + np.arange(len(pieces))
    rows = n_inner + len(pieces)
    buf = np.zeros(rows * B3_CHUNK, np.uint8)
    for p, data in enumerate(pieces):
        data = np.frombuffer(data, np.uint8) if not isinstance(data, np.ndarray) else data
        cut = (int(chunks[p]) - 1) * B3_CHUNK
        buf[int(inner_at[p]) * B3_CHUNK:int(inner_at[p]) * B3_CHUNK + cut] = data[:cut]
        buf[int(tail_row[p]) * B3_CHUNK:int(tail_row[p]) * B3_CHUNK + len(data) - cut] = data[cut:]
    words = buf.view("<u4").reshape(rows, B3_CHUNK // B3_BLOCK, 16)

    piece = np.concatenate([np.repeat(np.arange(len(pieces)), chunks - 1), by_blocks])  # piece of each lane
    counter = np.empty(rows, np.int64)  # the chunk's index in its piece
    counter[:n_inner] = np.arange(n_inner) - np.repeat(inner_at[:-1], chunks - 1)
    counter[n_inner:] = chunks[by_blocks] - 1
    last = np.full(rows, B3_CHUNK // B3_BLOCK - 1)
    last[n_inner:] = tail_blocks[by_blocks] - 1
    last_len = np.full(rows, B3_BLOCK)
    last_len[n_inner:] = tail_len[by_blocks] - B3_BLOCK * last[n_inner:]
    root = np.zeros(rows, bool)
    root[n_inner:] = chunks[by_blocks] == 1

    cv = np.repeat(B3_IV[:, None], rows, axis=1)
    for k in range(B3_CHUNK // B3_BLOCK):
        live = n_inner + int((tail_blocks > k).sum())
        end = last[:live] == k
        flags = np.where(end, CHUNK_END | np.where(root[:live], ROOT, 0), 0) | (CHUNK_START if k == 0 else 0)
        m = np.ascontiguousarray(words[:live, k, :].T)
        cv[:, :live] = b3_compress(cv[:, :live], m, counter[:live], np.where(end, last_len[:live], B3_BLOCK), flags)

    order = np.lexsort((counter, piece))  # each piece's chunks in order, piece after piece
    nodes, owner, out = cv[:, order], piece[order], [b""] * len(pieces)
    while nodes.shape[1]:
        count = np.bincount(owner, minlength=len(pieces))[owner]  # nodes its piece has at this level
        pos = np.arange(len(owner)) - np.searchsorted(owner, owner)
        done = count == 1
        for p, lane in zip(owner[done], np.flatnonzero(done)):
            out[p] = nodes[:, lane].astype("<u4").tobytes()
        keep = ~done & (pos % 2 == 0)  # a left child, or the odd one carried up
        pair = keep & (pos + 1 < count)
        left = np.flatnonzero(pair)
        merged = nodes[:, np.flatnonzero(keep)]
        merged[:, pair[keep]] = b3_compress(np.broadcast_to(B3_IV[:, None], (8, len(left))),
                                            np.concatenate([nodes[:, left], nodes[:, left + 1]]), 0, B3_BLOCK,
                                            np.where(count[left] == 2, PARENT | ROOT, PARENT))
        nodes, owner = merged, owner[keep]
    return out


def blake3_many(pieces: list) -> list[bytes]:
    """The 32-byte BLAKE3 hash of each piece (bytes or a u8 array), in
    batches of about ``B3_BATCH`` bytes."""
    out, batch, held = [], [], 0
    for piece in pieces:
        batch.append(piece)
        held += len(piece)
        if held >= B3_BATCH:
            out += _b3_batch(batch)
            batch, held = [], 0
    return out + (_b3_batch(batch) if batch else [])


def sha256_many(pieces: list) -> list[bytes]:
    return [hashlib.sha256(memoryview(p)).digest() for p in pieces]


DIGESTERS = {"sha256": sha256_many, "blake3": blake3_many}


def plain_chunks_many(datas: list, avg: int, chunking: str = "cdc", digester: str = "sha256") -> list[list]:
    """``plain_chunks`` of each file, the digests of all their chunks taken together."""
    cuts = [plain_cuts(data, avg, chunking) for data in datas]
    pieces = [data[s:e] for data, ends in zip(datas, cuts) for s, e in zip([0, *ends[:-1]], ends)]
    digests = iter(DIGESTERS[digester](pieces))
    return [[(e - s, next(digests)) for s, e in zip([0, *ends[:-1]], ends)] for ends in cuts]


def plain_chunks(data: np.ndarray, avg: int, chunking: str = "cdc",
                 digester: str = "sha256") -> list[tuple[int, bytes]]:
    """[(size, digest)] of one file's chunks."""
    return plain_chunks_many([data], avg, chunking, digester)[0]


def lz4_block_decode(src: bytes, size: int) -> bytes:
    """LZ4 block format, sequence by sequence."""
    out, i, n = bytearray(), 0, len(src)
    while i < n:
        token = src[i]
        i += 1
        lit = token >> 4
        if lit == 15:
            while True:
                lit += src[i]
                i += 1
                if src[i - 1] != 255:
                    break
        out += src[i:i + lit]
        i += lit
        if i >= n:
            break
        back = src[i] | (src[i + 1] << 8)
        i += 2
        run = token & 15
        if run == 15:
            while True:
                run += src[i]
                i += 1
                if src[i - 1] != 255:
                    break
        run += 4
        at = len(out) - back
        if back == 0 or at < 0:
            raise ValueError("lz4: offset outside the output")
        while run > 0:  # a match may overlap its own output
            piece = out[at:at + min(run, back)]
            out += piece
            at += len(piece)
            run -= len(piece)
    if len(out) != size:
        raise ValueError(f"lz4: decoded {len(out)} bytes, record says {size}")
    return bytes(out)


def zstd_frame_decode(src: bytes, size: int) -> bytes:
    """ONE standalone zstd frame, nothing before or after it: a frame that
    names a dictionary (or is no zstd frame, as a trained-dictionary chunk's
    own header is not) cannot be decoded without what it does not carry."""
    frame = zstandard.ZstdDecompressor().decompressobj()
    try:
        out = frame.decompress(bytes(src))
    except zstandard.ZstdError as e:
        raise ValueError(f"zstd: {e}") from e
    if not frame.eof or frame.unused_data:
        raise ValueError("zstd: not one whole frame")
    if len(out) != size:
        raise ValueError(f"zstd: decoded {len(out)} bytes, record says {size}")
    return out
