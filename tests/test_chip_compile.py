"""The main path's kernels, held to the TPU v5e compiler at real widths.

The chip's compiler is installed in the sandbox and compiles for a chip
that is described, not attached — so every PR learns here, at no chip
time, what Mosaic/XLA would refuse there (a misaligned slice, too much
VMEM, a program over HBM). Nothing runs; a compile that passes is not a
chip run (chip_smoke.py is).

Rules this file keeps (on-chip-measurement guide §2): the topology is
described inside a module-scoped fixture, never at import, in a skipif
or a parametrize argument; it compiles in the test's own process (the
worker that loaded libtpu holds its lock); one file, so one xdist worker
owns all of it; the persistent compile cache is off around the compiles
(an entry written for an unattached chip cannot be read back).
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from nydus_snapshotter_tpu.ops import (
    cdc,
    fused_convert,
    gear_pallas,
    probe_pallas,
    sha256_pallas,
)

V5E_HBM_BYTES = 16 * 10**9
CHUNK = 0x10000
MIB = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """shape(dims, dtype) -> ShapeDtypeStruct on one described v5e chip,
    with the persistent compile cache off for this module's compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes


def test_gear_bitmaps_kernel(shape):
    p = cdc.CDCParams(CHUNK)
    win = fused_convert.WINDOW
    compiled = gear_pallas.gear_bitmaps.lower(
        shape((16, win + gear_pallas.TAIL), jnp.uint8), p.mask_small, p.mask_large, win
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_pass1_with_pallas_gear_at_512_mib(shape, monkeypatch):
    """A 512 MiB layer: its buffer pads to 640 MiB. The branch is chosen
    at trace time from supported(), which asks JAX's default backend —
    the CPU here — so the test steers it."""
    monkeypatch.setattr(gear_pallas, "supported", lambda n: True)
    eng = fused_convert.FusedDeviceEngine(chunk_size=CHUNK)
    n = 512 * MIB
    npad = 640 * MIB
    compiled = fused_convert._pass1.lower(
        shape((npad,), jnp.uint8),
        shape((), jnp.int32),
        eng.params.mask_small,
        eng.params.mask_large,
        fused_convert._wcap_for(n, eng.params.bits + 2),
        fused_convert._wcap_for(n, eng.params.bits - 2),
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert _device_bytes(compiled) < V5E_HBM_BYTES


@pytest.mark.parametrize(
    "chunk_size,digester,caps_of,n_rows",
    [
        (CHUNK, "sha256", lambda top: (top >> 2, top >> 1, top), 8),
        (CHUNK, "blake3", lambda top: (top,), 8),
        # 65,536 blocks: the class of a 2-4 MiB chunk, with the fewest rows
        # the plan gives a class. (The class of max-size chunks above it,
        # 65,537 blocks, takes this sandbox two minutes, against six seconds.)
        (16 * CHUNK, "sha256", lambda top: (top - 1,), fused_convert.ROW_FLOOR),
        # the other regime: a layer of small files (benchmark/configs/
        # smallfiles-64k.json, 13.8k files, 87% of one chunk each) plans its
        # classes of 8-512 blocks at 2,048 rows each; the two largest here
        (CHUNK, "sha256", lambda top: (256, 512), 2048),
    ],
    ids=["64k-sha256", "64k-blake3", "1m-sha256-row-floor", "64k-sha256-smallfiles-2k-rows"],
)
def test_pass2_gather_digest(shape, monkeypatch, chunk_size, digester, caps_of, n_rows):
    """Few rows, real capacities: the top classes of a layer's plan at
    64 KiB chunks, the longest power-of-two class at the CLI's 1 MiB, and
    many rows at short capacities: the widest classes of a layer of small
    files. The digest rounds' form is chosen at trace time from JAX's
    default backend — the CPU here — so the test steers it to the
    unrolled form the chip compiles. That form costs the compiler
    seconds per class and a real layer brings twelve (sha256) or nine
    (blake3), so only the top ones are held here."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = fused_convert.FusedDeviceEngine(chunk_size=chunk_size, digester=digester)
    caps = caps_of(eng._blocks_of(eng.params.max_size))
    rows = tuple(shape((n_rows,), jnp.int32) for _ in caps)
    compiled = fused_convert._pass2.lower(
        shape((64 * MIB // 4,), jnp.uint32), rows, rows, caps, digester=digester
    ).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def _arrays_minor(text: str, n_elements: int, minor: tuple[int, ...]) -> set[str]:
    """The shapes in a compiled program's text that hold at least
    ``n_elements`` and whose minor dimension (by its layout's
    minor-to-major order) is one of ``minor``."""
    found = set()
    for m in re.finditer(r"\b[a-z]+[0-9]+\[([0-9,]+)\]\{([0-9,]+)", text):
        dims = [int(d) for d in m.group(1).split(",")]
        if math.prod(dims) >= n_elements and dims[int(m.group(2).split(",")[0])] in minor:
            found.add(m.group(0))
    return found


def test_pass2_row_tiles_hold_hbm_to_the_budget(shape, monkeypatch):
    """The widest class of benchmark/configs/mlimage-1m.json's layer: 576
    chunks of 1-2 MiB (32,768 blocks) in a 1,280 MiB buffer, in the row
    tiles the plan gives it. The temporaries are one tile's, three bytes
    a byte of it (the gathered rows, their transpose, the digest's
    blocks), and with the buffer they stay under half the chip's HBM. No
    array of the batch's size has a block's 16 words, or a word's 4
    bytes, on the lanes: the chip's compiler lays such a one out
    eightfold (16 words on 128 lanes), which made the byte gather's
    temporaries ten bytes a byte (5,122 MiB for this tile) and its
    untiled batch of bucket_rows(576) = 1,024 rows RESOURCE_EXHAUSTED
    (18.0 of 15.75 GiB). That batch now compiles too: 2 GiB of blocks in
    6,146 MiB of temporaries (TILE_BYTES is the plan's, and stays)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cap, live, buffer = 32768, 576, shape((1280 * MIB // 4,), jnp.uint32)
    rows, tile_rows = fused_convert.class_rows(live, cap * 64)
    assert (rows, tile_rows) == (768, 256) and rows < fused_convert.bucket_rows(live) == 1024
    tiled = (shape((rows // tile_rows, tile_rows), jnp.int32),)
    compiled = fused_convert._pass2.lower(buffer, tiled, tiled, (cap,)).compile()
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert 3 * fused_convert.TILE_BYTES <= temporaries <= 3 * fused_convert.TILE_BYTES + 4 * MIB
    assert _device_bytes(compiled) < V5E_HBM_BYTES // 2
    text = compiled.as_text()
    batch = tile_rows * cap * 16
    assert not _arrays_minor(text, batch, (4, 16))
    assert _arrays_minor(text, batch, (tile_rows,))  # the digest's blocks: rows on the lanes
    whole = (shape((fused_convert.bucket_rows(live),), jnp.int32),)
    compiled = fused_convert._pass2.lower(buffer, whole, whole, (cap,)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= 3 * 4 * fused_convert.TILE_BYTES + 4 * MIB


def test_pass2_with_pallas_probe(shape):
    cap, depth = 1 << 16, 8
    cp = probe_pallas.padded_slots(cap, depth)
    rows = (shape((16,), jnp.int32),)
    compiled = fused_convert._pass2.lower(
        shape((16 * MIB // 4,), jnp.uint32), rows, rows, (64,),
        shape((8, cp), jnp.int32), shape((cap,), jnp.int32), cap, depth,
        pallas_probe=True,
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_sha256_pallas_kernel(shape):
    compiled = sha256_pallas.sha256_batch_pallas.lower(
        shape((1024, 1025, 16), jnp.uint32), shape((1024,), jnp.int32)
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("n_queries", [512, 4096])
def test_probe_kernel_at_1m_entry_table(shape, n_queries):
    """A 1M-entry dict builds a 2M-slot table; one launch and the
    segment-mapped form both lower."""
    cap, depth = 1 << 21, 16
    cp = probe_pallas.padded_slots(cap, depth)
    compiled = probe_pallas.probe_padded.lower(
        shape((8, cp), jnp.int32), shape((cap,), jnp.int32),
        shape((n_queries, 8), jnp.uint32), table_cap=cap, depth=depth,
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert _device_bytes(compiled) < V5E_HBM_BYTES
