"""Pallas TPU kernel for the sharded-dict hash probe.

The XLA lowering of the dict probe is a gather: ``k[slots]`` with
``slots: u32[M, D]`` against a table ``u32[C, 8]``. On TPU, XLA executes
that gather effectively element-serially (parallel/sharded_dict.py's
crossover note). The TPU-native formulation is the one embedding-lookup
kernels use: keep the table in HBM and DMA each query's probe-chain
window into VMEM scratch with K outstanding copies so the per-query DMA
latency pipelines away. All compare/select work runs on the VPU over the
window; no XLA gather is ever emitted.

Table layout contract (prepared by ``pad_keys``) — LANE-DENSE: slots
run along the minor (lane) axis, digest words along the second-minor
(sublane) axis. Mosaic tiles HBM operands (8, 128) over the last two
dims, so the natural ``[C, 8]`` layout pads every row to 128 lanes and
cannot be sliced 8 wide; transposed, one table tile is exactly the 8
digest words of 128 consecutive slots:

- ``keys_t  i32[8, CP]`` — word ``w`` of slot ``s`` at ``[w, s]``: the
  open-addressing table followed by its own head (``slot mod C``), so a
  chain window starting anywhere in ``[0, C)`` never wraps.
- Window: ``W = align128(depth + 127)`` lanes from the 128-aligned
  ``wstart = slot0 & ~127``; the in-window chain offset (``slot0 & 127``)
  plus the chain depth always fits.

Only keys are DMA'd. The kernel answers the first key-equal slot of the
chain (or -1) and the value comes from one Q-element gather on the plain
``values i32[C]`` outside it. That equals "first key-equal slot with a
value" because the table is insert-only: every slot between an entry's
home and the entry is occupied, so the only key-equal slot without a
value is an empty one met by an all-zero query, and its value 0 is the
miss answer already.

Queries, window starts and results travel through SMEM as scalars (the
compare is one scalar-vs-row op per digest word), so one launch takes
``LAUNCH_QUERIES`` queries; ``probe_padded`` maps a longer batch over
launch-sized segments.

Correctness oracle: parallel/sharded_dict._probe_local (XLA gather
formulation) — differential-tested in tests/test_probe_pallas.py in
interpret mode; tests/test_chip_compile.py holds the kernel to the v5e
compiler at a 1M-entry table.

Reference correspondence: the chunk-dict probe inside ``nydus-image``
(pkg/converter/tool/builder.go:122-123 hands the builder a chunk dict;
the Rust builder probes it per chunk).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PIPELINE = 4  # outstanding DMA windows per query stream
LANES = 128
LAUNCH_QUERIES = 2048  # per kernel launch: 8+3 SMEM words per query


def window_slots(depth: int) -> int:
    """Lanes one query's DMA window spans."""
    return (depth + 2 * LANES - 1) & ~(LANES - 1)


def padded_slots(cap: int, depth: int) -> int:
    """CP: lanes of the wrap-free key table of a ``cap``-slot table."""
    return ((cap - 1) & ~(LANES - 1)) + window_slots(depth)


def pad_keys(keys: np.ndarray, depth: int) -> np.ndarray:
    """keys u32[C,8] -> wrap-free lane-dense keys_t i32[8,CP]."""
    cap = keys.shape[0]
    slot = np.arange(padded_slots(cap, depth)) % cap
    return np.ascontiguousarray(
        np.asarray(keys, dtype=np.uint32)[slot].T
    ).view(np.int32)


def _kernel(
    wstart_ref,  # SMEM i32[Q]   (scalar prefetch: 128-aligned window starts)
    off_ref,  # SMEM i32[Q]      (scalar prefetch: slot0 - wstart)
    q_ref,  # SMEM i32[Q*8]      (scalar prefetch: query words, row-major)
    keys_ref,  # ANY  i32[8, CP]
    out_ref,  # SMEM i32[Q]      (padded slot of the first key match, or -1)
    kscratch,  # VMEM i32[K, 8, W]
    ksem,  # DMA sems [K]
    *,
    depth: int,
    n_queries: int,
):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w = window_slots(depth)
    k = PIPELINE

    def copy(i):
        sl = jax.lax.rem(i, k)
        win = pl.ds(pl.multiple_of(wstart_ref[i], LANES), w)
        return pltpu.make_async_copy(keys_ref.at[:, win], kscratch.at[sl], ksem.at[sl])

    # Prologue: fill the pipeline.
    for i in range(min(k, n_queries)):
        copy(i).start()

    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)

    def body(i, _):
        sl = jax.lax.rem(i, k)
        copy(i).wait()
        win_k = kscratch[sl]  # i32[8, W]
        off = off_ref[i]
        match = (lanes >= off) & (lanes < off + depth)
        for word in range(8):
            match &= win_k[word : word + 1, :] == q_ref[i * 8 + word]
        # first match in chain order: smallest matching lane
        first = jnp.min(jnp.where(match, lanes, jnp.int32(w)))
        out_ref[i] = jnp.where(first < w, wstart_ref[i] + first, -1)

        @pl.when(i + k < n_queries)
        def _():
            copy(i + k).start()

        return ()

    jax.lax.fori_loop(0, n_queries, body, ())


def _launch(keys_t, queries, wstart, off, depth: int, interpret: bool):
    """One kernel launch: queries i32[Q*8], wstart/off i32[Q] -> i32[Q]
    padded slots (-1 = no key match)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q = wstart.shape[0]
    w = window_slots(depth)
    return pl.pallas_call(
        functools.partial(_kernel, depth=depth, n_queries=q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            # the table stays in ANY (HBM): only ever touched via DMA
            in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM),
            scratch_shapes=[
                pltpu.VMEM((PIPELINE, 8, w), jnp.int32),
                pltpu.SemaphoreType.DMA((PIPELINE,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((q,), jnp.int32),
        interpret=interpret,
    )(wstart, off, queries, keys_t)


@functools.partial(jax.jit, static_argnames=("table_cap", "depth", "interpret"))
def probe_padded(
    keys_t: jax.Array,
    values: jax.Array,
    queries: jax.Array,
    table_cap: int,
    depth: int,
    interpret: bool = False,
) -> jax.Array:
    """Probe queries u32[Q,8] against the pad_keys() layout of a
    ``table_cap``-slot table and its plain values i32[C] -> i32[Q]
    (0 = miss)."""
    q = queries.shape[0]
    slot0 = (queries[:, 1] & jnp.uint32(table_cap - 1)).astype(jnp.int32)
    wstart = slot0 & ~jnp.int32(LANES - 1)
    off = slot0 - wstart
    words = jax.lax.bitcast_convert_type(queries, jnp.int32)
    if q <= LAUNCH_QUERIES:
        slot = _launch(keys_t, words.reshape(-1), wstart, off, depth, interpret)
    else:
        # zero-padded tail queries probe slot 0 and are sliced away
        segs = -(-q // LAUNCH_QUERIES)
        pad = segs * LAUNCH_QUERIES - q
        slot = jax.lax.map(
            lambda xs: _launch(keys_t, xs[0], xs[1], xs[2], depth, interpret),
            (
                jnp.pad(words, ((0, pad), (0, 0))).reshape(segs, LAUNCH_QUERIES * 8),
                jnp.pad(wstart, (0, pad)).reshape(segs, LAUNCH_QUERIES),
                jnp.pad(off, (0, pad)).reshape(segs, LAUNCH_QUERIES),
            ),
        ).reshape(-1)[:q]
    # the padded table repeats its head: slot mod C is the table's slot
    return jnp.where(slot >= 0, values[slot & jnp.int32(table_cap - 1)], 0)


def probe(
    keys: np.ndarray,
    values: np.ndarray,
    queries: np.ndarray,
    depth: int,
    interpret: bool = False,
) -> np.ndarray:
    """Convenience single-shard probe: builds the padded key layout and
    runs the kernel. Returns i32[M] (0 = miss; hits are dict index + 1, the
    table's value convention)."""
    queries = np.ascontiguousarray(queries, dtype=np.uint32).reshape(-1, 8)
    return np.asarray(
        probe_padded(
            jnp.asarray(pad_keys(keys, depth)),
            jnp.asarray(np.asarray(values, dtype=np.int32).reshape(-1)),
            jnp.asarray(queries),
            keys.shape[0],
            depth,
            interpret=interpret,
        )
    )


def supported() -> bool:
    """The compiled kernel is for tpu backends (tests pass
    ``interpret=True`` themselves)."""
    return jax.default_backend() == "tpu"
