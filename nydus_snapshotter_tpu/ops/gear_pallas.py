"""Pallas TPU kernel for the gear-hash candidate bitmaps.

The XLA formulation of the gear pass (ops/gear.py windowed_gear_sum +
ops/chunker._hash_bitmaps_kernel) materializes every doubling step in HBM
(~1.5 GiB/s measured on a v5e chip). This kernel keeps the whole pipeline —
mix32, the 5 log-doubling shifted adds, both mask tests, and the bitmap
pack — in VMEM, reading each input byte from HBM exactly once.

Layout: lane-major substreams. A window of T bytes is split into 128
substreams of SW = T/128 consecutive bytes; substream l lives in lane l,
successive bytes in successive sublanes (rows). The windowed sum's
"position - m" then shifts *rows* (cheap sublane slice) instead of lanes.
Each substream tile carries the 31 bytes preceding it (the previous
substream's tail, or the window's host-provided tail for lane 0) so hashes
are bit-identical to whole-stream hashing — the same seam-carry discipline
as the host windowing (ops/chunker.py).

Outputs are packed u32 bitmap words per substream ([B, SW/32, 128]);
``gear_bitmaps`` transposes them back to stream order so the host-side
candidate unpack (ops/chunker._unpack_positions) is layout-agnostic.

Reference hot loop replaced: chunking inside ``nydus-image create``
(pkg/converter/tool/builder.go:148-178).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from nydus_snapshotter_tpu.ops import gear

TAIL = gear.GEAR_WINDOW - 1  # 31
PAD = 32  # top pad rows per tile: TAIL carry rows + 1 zero row for 8-row
#          DMA alignment (Mosaic requires sublane slices aligned to 8; the
#          zero row sits 32 positions back and can never reach a valid hash)
LANES = 128
# Output rows per grid step. Tunable via NTPU_GEAR_TILE for hardware
# sweeps (suspected VMEM-pressure bound at 4096: ~6 live u32[rows,128]
# temporaries; 1024 keeps them ~3 MB total).
import os as _os

ROWS_PER_TILE = int(_os.environ.get("NTPU_GEAR_TILE", "1024"))


def _kernel(y_ref, out_s_ref, out_l_ref, scratch, sem, *, mask_s: int, mask_l: int):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    t = pl.program_id(1)
    r = ROWS_PER_TILE
    dma = pltpu.make_async_copy(
        y_ref.at[b, pl.ds(t * r, r + PAD), :], scratch, sem
    )
    dma.start()
    dma.wait()

    g = gear.mix32_jnp(scratch[:])  # u32[r+32, 128]
    s = g
    m = 1
    while m < gear.GEAR_WINDOW:
        shifted = jnp.concatenate(
            [jnp.zeros((m, LANES), jnp.uint32), s[:-m]], axis=0
        )
        s = s + (shifted << np.uint32(m))
        m *= 2
    h = s[PAD:]  # u32[r, 128], h[i] = gear hash ending at substream pos i

    # Pack in int32 (Mosaic has no unsigned reductions); the weighted sum of
    # distinct powers of two is the same bit pattern under two's complement.
    w = jnp.left_shift(
        jnp.int32(1), jax.lax.broadcasted_iota(jnp.int32, (1, 32, 1), 1)
    )

    def pack(bits):
        packed = jnp.sum(bits.reshape(r // 32, 32, LANES) * w, axis=1)
        return jax.lax.bitcast_convert_type(packed, jnp.uint32)

    out_s_ref[:] = pack(((h & np.uint32(mask_s)) == 0).astype(jnp.int32))
    out_l_ref[:] = pack(((h & np.uint32(mask_l)) == 0).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("mask_s", "mask_l", "interpret"))
def _bitmaps_lanes(y: jax.Array, mask_s: int, mask_l: int, interpret: bool = False):
    """y: u8[B, SW+32, 128] (lane-major substreams; 1 zero row + 31 tail
    rows on top) -> (u32[B, SW/32, 128], u32[B, SW/32, 128]) packed per
    substream."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, swp, _ = y.shape
    sw = swp - PAD
    grid = (bsz, sw // ROWS_PER_TILE)
    out_shape = jax.ShapeDtypeStruct((bsz, sw // 32, LANES), jnp.uint32)
    out_spec = pl.BlockSpec(
        (1, ROWS_PER_TILE // 32, LANES), lambda b, t: (b, t, 0)
    )
    kernel = functools.partial(_kernel, mask_s=mask_s, mask_l=mask_l)

    def kernel_squeezed(y_ref, os_ref, ol_ref, scratch, sem):
        # out blocks arrive as [1, r/32, 128]; present 2-D views to _kernel
        class _V:
            def __init__(self, ref):
                self.ref = ref

            def __setitem__(self, idx, val):
                self.ref[0] = val

        kernel(y_ref, _V(os_ref), _V(ol_ref), scratch, sem)

    return pl.pallas_call(
        kernel_squeezed,
        grid=grid,
        out_shape=(out_shape, out_shape),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(out_spec, out_spec),
        scratch_shapes=[
            pltpu.VMEM((ROWS_PER_TILE + PAD, LANES), jnp.uint8),
            pltpu.SemaphoreType.DMA(()),
        ],
        interpret=interpret,
    )(y)


@functools.partial(jax.jit, static_argnames=("mask_s", "mask_l", "n", "interpret"))
def gear_bitmaps(x: jax.Array, mask_s: int, mask_l: int, n: int, interpret: bool = False):
    """Drop-in device path for ops/chunker._hash_bitmaps_kernel.

    x: u8[B, n+31] stream-order windows with 31-byte tail prefix.
    Returns (u32[B, n//32], u32[B, n//32]) packed candidate bitmaps in
    stream order for the small/large FastCDC masks.
    """
    bsz = x.shape[0]
    sw = n // LANES
    seg = x[:, TAIL:].reshape(bsz, LANES, sw).transpose(0, 2, 1)  # [B, SW, 128]
    tails = jnp.concatenate(
        [x[:, :TAIL, None], seg[:, sw - TAIL :, : LANES - 1]], axis=2
    )  # [B, 31, 128]: 31 bytes preceding each substream
    zrow = jnp.zeros((bsz, 1, LANES), jnp.uint8)
    y = jnp.concatenate([zrow, tails, seg], axis=1)  # [B, SW+32, 128]
    bm_s, bm_l = _bitmaps_lanes(y, mask_s, mask_l, interpret=interpret)
    # substream-major words -> stream order: [B, SW/32, 128] -> [B, n/32]
    return (
        bm_s.transpose(0, 2, 1).reshape(bsz, n // 32),
        bm_l.transpose(0, 2, 1).reshape(bsz, n // 32),
    )


def supported(n: int) -> bool:
    """This kernel needs TPU and a window that tiles into lane substreams."""
    return jax.default_backend() == "tpu" and n % (LANES * ROWS_PER_TILE) == 0
