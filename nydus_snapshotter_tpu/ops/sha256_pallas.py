"""Pallas TPU kernel for batched SHA-256.

The XLA formulation (ops/sha256.py: vmap over chunks, lax.scan over
blocks) measured 2.8 GiB/s on a v5e chip — adequate but likely layout- and
scan-overhead-bound rather than VPU-bound. This kernel pins the layout:
chunks live in lanes (8 sublanes x 128 lanes = 1024 chunks per grid step),
the eight working variables are [8, 128] vectors, and the message schedule
is a rolling 16-deep window kept as sixteen separate [8, 128] vectors.

Two backend constraints shape the round loop, learned the hard way:

- Mosaic cannot lower `dynamic_slice` on *values* — the first real-TPU
  compile failed exactly there when the
  message window was a stacked [16, 8, 128] array indexed by
  ``(step*8 + r) % 16`` with a traced step.
- XLA CPU (the `interpret=True` correctness path) chokes on a fully
  64-round-unrolled body — minutes of compile even at one block
  (the same issue ops/sha256.py documents).

So: rounds run 8-per-step inside a ``fori_loop`` of 8 steps, the window
*rotates* — every round consumes ``w[0]`` and appends the (conditionally
extended) word at the tail, so all window indices are static Python ints —
and the round constant is picked by a chain of scalar selects over the
step index, so there is no K-table indexing at all. The per-block loop
is the second grid dimension: each step's 64-word block arrives via the
BlockSpec index map and the running hash state lives in the revisited
output block (the standard accumulation pattern), so the kernel contains
no dynamic ref indexing either.

Data layout in: ``u32[G, B, 16, 8, 128]`` (word-major per block, chunk
groups minor) produced by one device-side transpose from the engine's
``u32[M, B, 16]`` packing; counts ``i32[G, 8, 128]``. Out:
``u32[G, 8, 8, 128]`` (state words major) transposed back to ``u32[M, 8]``.

Same math as ops/sha256.py `_compress_unrolled` — differential-tested
equal; usable under `interpret=True` on CPU for correctness runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from nydus_snapshotter_tpu.ops import sha256 as sha_ref

LANES = 128
SUBLANES = 8
GROUP = LANES * SUBLANES  # chunks per grid step


def _rotr(x, r):
    return (x >> np.uint32(r)) | (x << np.uint32(32 - r))


_ROUND_UNROLL = 8  # rounds per fori step: compile size vs loop overhead


def _k_at(s, r: int):
    """Round constant K[s*8 + r] for traced step s, static in-step round r.

    A chain of 7 scalar selects replaces any table load — Mosaic lowers
    arith.select fine, and there is nothing to dynamic-slice.
    """
    out = jnp.uint32(sha_ref._K[r])
    for row in range(1, 8):
        out = jnp.where(s == row, np.uint32(sha_ref._K[row * 8 + r]), out)
    return out


def _kernel(blocks_ref, counts_ref, out_ref):
    """blocks_ref: u32[1, 1, 16, 8, 128] (this grid step's block);
    counts_ref: i32[1, 8, 128]; out_ref: u32[1, 8, 8, 128], revisited
    across the block grid dim — it carries the running hash state."""
    import jax.experimental.pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        for i in range(8):
            out_ref[0, i] = jnp.full(
                (SUBLANES, LANES), np.uint32(sha_ref._H0[i])
            )

    state = [out_ref[0, i] for i in range(8)]
    w0 = blocks_ref[0, 0]  # u32[16, 8, 128]

    def rounds8(s, carry):
        *w, a, b, c, d, e, f, g, h = carry
        # Rounds t = s*8 + r. The window rotates: at round t, w[0] is
        # W[t] for t < 16 (pure rotation of the initial 16 words) and
        # W[t-16] for t >= 16, where the schedule extension
        #   W[t] = W[t-16] + s0(W[t-15]) + W[t-7] + s1(W[t-2])
        # reads w[0], w[1], w[9], w[14]. t >= 16 iff s >= 2, uniform
        # across the unrolled step.
        extend = s >= 2
        for r in range(_ROUND_UNROLL):
            w15, w2 = w[1], w[14]
            es0 = _rotr(w15, 7) ^ _rotr(w15, 18) ^ (w15 >> np.uint32(3))
            es1 = _rotr(w2, 17) ^ _rotr(w2, 19) ^ (w2 >> np.uint32(10))
            wi = w[0] + jnp.where(extend, es0 + w[9] + es1, np.uint32(0))
            w = w[1:] + [wi]
            s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
            ch = (e & f) ^ (~e & g)
            t1 = h + s1 + ch + _k_at(s, r) + wi
            s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
            maj = (a & b) ^ (a & c) ^ (b & c)
            a, b, c, d, e, f, g, h = t1 + s0 + maj, a, b, c, d + t1, e, f, g
        return (*w, a, b, c, d, e, f, g, h)

    out = jax.lax.fori_loop(0, 8, rounds8, (*[w0[i] for i in range(16)], *state))
    live = j < counts_ref[0]  # chunks with fewer blocks keep their state
    for i, (new, old) in enumerate(zip(out[16:], state)):
        out_ref[0, i] = jnp.where(live, new + old, old)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sha256_groups(blocks_t: jax.Array, counts_t: jax.Array, interpret: bool = False):
    import jax.experimental.pallas as pl

    g, b = blocks_t.shape[0], blocks_t.shape[1]
    return pl.pallas_call(
        _kernel,
        grid=(g, b),
        in_specs=[
            pl.BlockSpec((1, 1, 16, SUBLANES, LANES), lambda i, j: (i, j, 0, 0, 0)),
            pl.BlockSpec((1, SUBLANES, LANES), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 8, SUBLANES, LANES), lambda i, j: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, 8, SUBLANES, LANES), jnp.uint32),
        interpret=interpret,
    )(blocks_t, counts_t)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sha256_batch_pallas(
    blocks: jax.Array, nblocks: jax.Array, interpret: bool = False
) -> jax.Array:
    """Drop-in for ops/sha256.sha256_batch: u32[M,B,16] + i32[M] -> u32[M,8].

    M is padded up to a multiple of 1024 internally (pad rows carry zero
    block counts and are sliced off).
    """
    m, b, _ = blocks.shape
    pad = (-m) % GROUP
    if pad:
        blocks = jnp.concatenate(
            [blocks, jnp.zeros((pad, b, 16), jnp.uint32)], axis=0
        )
        nblocks = jnp.concatenate([nblocks, jnp.zeros(pad, jnp.int32)])
    g = (m + pad) // GROUP
    # [M, B, 16] -> [G, B, 16, 8, 128]: chunks into (sublane, lane) minors.
    blocks_t = blocks.reshape(g, SUBLANES, LANES, b, 16).transpose(0, 3, 4, 1, 2)
    counts_t = nblocks.reshape(g, SUBLANES, LANES)
    states = _sha256_groups(blocks_t, counts_t, interpret=interpret)
    # [G, 8, 8, 128] -> [M, 8]
    out = states.transpose(0, 2, 3, 1).reshape(g * GROUP, 8)
    return out[:m]


def supported(m: int) -> bool:
    """Worth dispatching: TPU backend and a batch big enough to fill at
    least one 1024-chunk group."""
    return jax.default_backend() == "tpu" and m >= GROUP
