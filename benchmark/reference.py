"""The plain reference: what a RAFS convert has to say about a file's bytes.

Imports nothing of the program and takes nothing it has made. Written from
the format's rules: gear-v2 table ``G[b] = fmix32((b + 1) * 0x9E3779B1)``,
32-bit gear hash ``h_i = (h_{i-1} << 1) + G[x_i]``, FastCDC with
normalisation level 2 (min = avg/4, max = 4*avg, masks of bits+2 / bits-2
low bits), sha256 per chunk, lz4 block format for the stored bytes.
"""

from __future__ import annotations

import hashlib

import numpy as np


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


def gear_hashes(data: np.ndarray) -> np.ndarray:
    """h at every position: the hash forgets bytes older than 32 positions,
    so it is the sum of G over the window ending there, each shifted by its
    age. Built by doubling: a window of 2w is a window of w plus the window
    of w that ended w positions earlier, shifted by w."""
    h = np.concatenate([np.zeros(31, np.uint32), GEAR[data]])
    for w in (1, 2, 4, 8, 16):
        h[w:] += h[:-w] << np.uint32(w)  # the right side is evaluated before the add
    # the 31 positions a zero history would reach are never judged (min >= 32)
    return h[31:]


def plain_cuts(data: np.ndarray, avg: int) -> list[int]:
    """Chunk ends (exclusive) of one file, byte-sequential FastCDC."""
    n = len(data)
    bits = avg.bit_length() - 1
    lo, hi = avg // 4, 4 * avg
    mask_s, mask_l = np.uint32((1 << (bits + 2)) - 1), np.uint32((1 << (bits - 2)) - 1)
    h = gear_hashes(data) if n > lo else None
    cuts, start = [], 0
    while n - start > lo:
        end = None
        a, b = start + lo - 1, min(start + avg - 1, n)  # candidate i: chunk ends at i + 1
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
    return cuts


def plain_chunks(data: np.ndarray, avg: int) -> list[tuple[int, bytes]]:
    """[(size, sha256)] of one file's chunks."""
    out, start = [], 0
    for end in plain_cuts(data, avg):
        out.append((end - start, hashlib.sha256(memoryview(data[start:end])).digest()))
        start = end
    return out


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
