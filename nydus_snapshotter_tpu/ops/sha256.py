"""SHA-256 on device, vmapped across many chunks.

The reference digests every chunk with SHA-256 inside the Rust builder
(digest parity surface: pkg/converter/convert_unix.go:870 uses
``digest.SHA256``). Here the compression function runs as pure uint32 jnp
lanes — TPU has no 64-bit integers, and SHA-256 is natively a 32-bit
algorithm, so state and message schedule live in uint32 exactly.

Shape discipline: one chunk = a row of 64-byte blocks (``uint32[B, 16]``
big-endian words, standard SHA padding applied host-side). A batch of chunks
is ``uint32[M, B, 16]`` + per-chunk block counts; ``lax.scan`` walks the
block axis over ``uint32[B, 16, M]``, the chunks on the last axis, so a step
reads one ``[16, M]`` block and the VPU sees M-wide vector ops per round
(``_sha256_lanes``; the fused lane's pass 2 builds that form itself). Chunks
with fewer blocks carry masked (ignored) tail blocks — bucketing by size
class keeps the padding waste bounded (parallel/pipeline.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_K = np.array(
    [
        0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5, 0x3956C25B, 0x59F111F1,
        0x923F82A4, 0xAB1C5ED5, 0xD807AA98, 0x12835B01, 0x243185BE, 0x550C7DC3,
        0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7, 0xC19BF174, 0xE49B69C1, 0xEFBE4786,
        0x0FC19DC6, 0x240CA1CC, 0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA,
        0x983E5152, 0xA831C66D, 0xB00327C8, 0xBF597FC7, 0xC6E00BF3, 0xD5A79147,
        0x06CA6351, 0x14292967, 0x27B70A85, 0x2E1B2138, 0x4D2C6DFC, 0x53380D13,
        0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85, 0xA2BFE8A1, 0xA81A664B,
        0xC24B8B70, 0xC76C51A3, 0xD192E819, 0xD6990624, 0xF40E3585, 0x106AA070,
        0x19A4C116, 0x1E376C08, 0x2748774C, 0x34B0BCB5, 0x391C0CB3, 0x4ED8AA4A,
        0x5B9CCA4F, 0x682E6FF3, 0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
        0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
    ],
    dtype=np.uint32,
)

_H0 = np.array(
    [0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
     0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19],
    dtype=np.uint32,
)


def _rotr(x, r):
    return (x >> np.uint32(r)) | (x << np.uint32(32 - r))


def _compress_unrolled(state: jax.Array, block: jax.Array) -> jax.Array:
    """One SHA-256 compression: state u32[8] x block u32[16] -> u32[8]
    (or [8, M] x [16, M] -> [8, M]: M messages, one a lane).

    Fully unrolled — rounds and the message schedule live in registers as a
    flat chain of elementwise ops (a rolling 16-deep window replaces the
    w[64] array). The only sequential loop in the whole digest is the scan
    over blocks; XLA TPU fuses each unrolled compression into a few vector
    kernels, which keeps per-block dispatch overhead off the hot path (a
    fori_loop per round costs ~µs per iteration — 100x slower end-to-end at
    real chunk sizes). The XLA *CPU* backend chokes on this graph (LLVM
    spends minutes on the 600-op scalar chain), so CPU uses the looped
    variant below — same math, differential-tested equal.
    """
    w = [block[i] for i in range(16)]
    a, b, c, d, e, f, g, h = (state[i] for i in range(8))
    for i in range(64):
        if i < 16:
            wi = w[i]
        else:
            w15, w2 = w[(i - 15) % 16], w[(i - 2) % 16]
            s0 = _rotr(w15, 7) ^ _rotr(w15, 18) ^ (w15 >> np.uint32(3))
            s1 = _rotr(w2, 17) ^ _rotr(w2, 19) ^ (w2 >> np.uint32(10))
            wi = w[i % 16] + s0 + w[(i - 7) % 16] + s1
            w[i % 16] = wi
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + np.uint32(_K[i]) + wi
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + maj
        a, b, c, d, e, f, g, h = t1 + t2, a, b, c, d + t1, e, f, g
    return jnp.stack([a, b, c, d, e, f, g, h]) + state


def _compress_looped(state: jax.Array, block: jax.Array) -> jax.Array:
    """Loop-structured compression for backends where unrolling is hostile
    to the compiler (XLA CPU). Same math as _compress_unrolled."""
    k = jnp.asarray(_K)

    def schedule(i, w):
        s0 = _rotr(w[i - 15], 7) ^ _rotr(w[i - 15], 18) ^ (w[i - 15] >> np.uint32(3))
        s1 = _rotr(w[i - 2], 17) ^ _rotr(w[i - 2], 19) ^ (w[i - 2] >> np.uint32(10))
        return w.at[i].set(w[i - 16] + s0 + w[i - 7] + s1)

    w = jnp.zeros((64, *block.shape[1:]), dtype=jnp.uint32).at[:16].set(block)
    w = jax.lax.fori_loop(16, 64, schedule, w)

    def round_fn(i, s):
        a, b, c, d, e, f, g, h = s
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + k[i] + w[i]
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        return (t1 + s0 + maj, a, b, c, d + t1, e, f, g)

    out = jax.lax.fori_loop(0, 64, round_fn, tuple(state[i] for i in range(8)))
    return jnp.stack(out) + state


def _sha256_lanes(blocks: jax.Array, nblocks: jax.Array, unroll: bool) -> jax.Array:
    """Digest M padded messages laid out with the messages on the last
    axis: blocks u32[B, 16, M], nblocks i32[M] -> u32[M, 8]. The one digest
    loop: a step takes a [16, M] block into a state of [8, M], every round
    an M-wide vector op, and no array has the 16 words of a block (on the
    TPU: 16 of 128 lanes) as its last dimension.

    The blocks a message has left are carried beside the state and counted
    down, not compared with a step index: a loop that reads ``nblocks``
    (which the chip's compiler leaves in HBM) every step takes 3.6 us a
    step at 128 messages and 4.5 at 256 inside the fused lane's pass 2,
    this one 2.9 and 4.0 (my chip run, PR 34)."""
    compress = _compress_unrolled if unroll else _compress_looped

    def step(carry, block):
        state, left = carry
        return (jnp.where(left > 0, compress(state, block), state), left - 1), None

    init = jnp.broadcast_to(jnp.asarray(_H0)[:, None], (8, blocks.shape[2]))
    (state, _), _ = jax.lax.scan(step, (init, nblocks), blocks)
    return state.T


@functools.partial(jax.jit, static_argnames=("unroll",))
def _sha256_batch_jit(blocks: jax.Array, nblocks: jax.Array, unroll: bool) -> jax.Array:
    return _sha256_lanes(blocks.transpose(1, 2, 0), nblocks, unroll)


def sha256_batch(blocks: jax.Array, nblocks: jax.Array) -> jax.Array:
    """Digest a batch: blocks u32[M,B,16], nblocks i32[M] -> u32[M,8].

    ``NTPU_SHA_PALLAS=1`` routes large TPU batches through the Pallas
    kernel (ops/sha256_pallas.py) — opt-in until its throughput is
    measured against the XLA scan on real hardware (tools/devbench.py
    --stage sha measures both).
    """
    import os

    if os.environ.get("NTPU_SHA_PALLAS", "") not in ("", "0"):
        from nydus_snapshotter_tpu.ops import sha256_pallas

        if sha256_pallas.supported(blocks.shape[0]):
            return sha256_pallas.sha256_batch_pallas(blocks, nblocks)
    unroll = jax.default_backend() != "cpu"
    return _sha256_batch_jit(blocks, nblocks, unroll)


# ---------------------------------------------------------------------------
# Host-side packing
# ---------------------------------------------------------------------------


def n_padded_blocks(length: int) -> int:
    """Number of 64-byte blocks after standard SHA padding."""
    return (length + 8) // 64 + 1


def pad_message_np(data: bytes | np.ndarray) -> np.ndarray:
    """Standard SHA-256 padding -> big-endian words u32[nblocks, 16]."""
    arr = np.frombuffer(bytes(data), dtype=np.uint8) if not isinstance(data, np.ndarray) else data
    n = arr.size
    nb = n_padded_blocks(n)
    buf = np.zeros(nb * 64, dtype=np.uint8)
    buf[:n] = arr
    buf[n] = 0x80
    buf[-8:] = np.frombuffer((n * 8).to_bytes(8, "big"), dtype=np.uint8)
    return buf.view(">u4").astype(np.uint32).reshape(nb, 16)


def pack_messages_np(
    msgs: list[bytes], block_capacity: int | None = None
) -> tuple[np.ndarray, np.ndarray]:
    """Pack messages into a fixed-shape batch (u32[M,B,16], i32[M])."""
    counts = np.asarray([n_padded_blocks(len(m)) for m in msgs], dtype=np.int32)
    cap = block_capacity or (int(counts.max()) if len(msgs) else 1)
    if len(msgs) and int(counts.max()) > cap:
        raise ValueError(f"message needs {int(counts.max())} blocks > capacity {cap}")
    out = np.zeros((len(msgs), cap, 16), dtype=np.uint32)
    for i, m in enumerate(msgs):
        out[i, : counts[i]] = pad_message_np(m)
    return out, counts


def digest_to_bytes(state: np.ndarray) -> bytes:
    """u32[8] state -> canonical 32-byte big-endian digest."""
    return np.asarray(state, dtype=">u4").tobytes()


def sha256_many(msgs: list[bytes]) -> list[bytes]:
    """Digest many messages on device; returns raw 32-byte digests."""
    if not msgs:
        return []
    blocks, counts = pack_messages_np(msgs)
    states = np.asarray(jax.device_get(sha256_batch(jnp.asarray(blocks), jnp.asarray(counts))))
    return [digest_to_bytes(states[i]) for i in range(len(msgs))]
