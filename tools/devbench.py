"""Per-stage device micro-benchmark on the real TPU chip.

Measures each data-plane stage in isolation so kernel work is driven by
data, not vibes (VERDICT r1 "what's weak" #3):

  gear-bitmap : windowed position-parallel gear hash -> packed candidate bitmaps
  sha256      : bucketed batch digesting
  dict-probe  : sharded HBM chunk-dict lookup

Usage: python tools/devbench.py [--mib N] [--stage all|gear|sha|probe]
Prints one JSON line per stage: {stage, gibps, ms, shape, backend}.
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


def timeit(fn, *argsets, reps=6):
    """Min wall time over reps, forcing a device->host readback each rep.

    ``argsets`` is a list of distinct input tuples cycled across reps so a
    backend that caches per-input results can't fake the number; the D2H
    copy of (a slice of) the output is the sync barrier.
    """
    import jax

    def force(out):
        leaves = jax.tree_util.tree_leaves(out)
        return [np.asarray(jax.device_get(leaf.ravel()[:8])) for leaf in leaves]

    force(fn(*argsets[0]))  # warm-up / compile
    best = float("inf")
    out = None
    for i in range(reps):
        args = argsets[i % len(argsets)]
        t = time.perf_counter()
        out = fn(*args)
        force(out)
        best = min(best, time.perf_counter() - t)
    return best, out


def bench_gear(total_mib: int, window: int = 1 << 22, force_xla: bool = False):
    import jax
    import jax.numpy as jnp

    from nydus_snapshotter_tpu.ops import gear, gear_pallas
    from nydus_snapshotter_tpu.ops.chunker import _hash_bitmaps_kernel

    n_windows = max(1, (total_mib << 20) // window)
    tail = gear.GEAR_WINDOW - 1
    rng = np.random.default_rng(0)
    rows = rng.integers(0, 256, (n_windows, tail + window), dtype=np.uint8)
    x = jnp.asarray(rows)
    x2 = jnp.asarray(rng.integers(0, 256, rows.shape, dtype=np.uint8))
    mask_s, mask_l = 0x3FFFF, 0x3FFF

    use_pallas = gear_pallas.supported(window) and not force_xla
    if use_pallas:
        fn = lambda a: gear_pallas.gear_bitmaps(a, mask_s, mask_l, window)  # noqa: E731
    else:
        fn = lambda a: _hash_bitmaps_kernel(  # noqa: E731
            a, jnp.uint32(mask_s), jnp.uint32(mask_l), window
        )
    dt, _ = timeit(fn, (x,), (x2,))
    nbytes = n_windows * window
    return {
        "stage": "gear-bitmap",
        "gibps": round(nbytes / dt / (1 << 30), 3),
        "ms": round(dt * 1e3, 2),
        "shape": list(rows.shape),
        "backend": jax.default_backend(),
        "kernel": "pallas" if use_pallas else "xla",
    }


def bench_sha(total_mib: int, chunk_kib: int = 64):
    import jax
    import jax.numpy as jnp

    from nydus_snapshotter_tpu.ops import sha256

    chunk = chunk_kib << 10
    m = max(1, (total_mib << 20) // chunk)
    cap = sha256.n_padded_blocks(chunk)
    rng = np.random.default_rng(1)
    blocks = rng.integers(0, 2**32, (m, cap, 16), dtype=np.uint32)
    blocks2 = rng.integers(0, 2**32, (m, cap, 16), dtype=np.uint32)
    counts = np.full(m, cap, dtype=np.int32)
    bj, cj = jnp.asarray(blocks), jnp.asarray(counts)
    bj2 = jnp.asarray(blocks2)

    dt, _ = timeit(sha256.sha256_batch, (bj, cj), (bj2, cj))
    nbytes = m * chunk
    return {
        "stage": "sha256",
        "gibps": round(nbytes / dt / (1 << 30), 3),
        "ms": round(dt * 1e3, 2),
        "shape": [m, cap, 16],
        "backend": jax.default_backend(),
    }


def bench_sha_pallas(total_mib: int, chunk_kib: int = 64):
    import jax
    import jax.numpy as jnp

    from nydus_snapshotter_tpu.ops import sha256, sha256_pallas

    chunk = chunk_kib << 10
    m = max(1024, (total_mib << 20) // chunk)
    cap = sha256.n_padded_blocks(chunk)
    rng = np.random.default_rng(1)
    blocks = rng.integers(0, 2**32, (m, cap, 16), dtype=np.uint32)
    blocks2 = rng.integers(0, 2**32, (m, cap, 16), dtype=np.uint32)
    counts = np.full(m, cap, dtype=np.int32)
    bj, cj = jnp.asarray(blocks), jnp.asarray(counts)
    bj2 = jnp.asarray(blocks2)

    dt, _ = timeit(sha256_pallas.sha256_batch_pallas, (bj, cj), (bj2, cj))
    nbytes = m * chunk
    return {
        "stage": "sha256-pallas",
        "gibps": round(nbytes / dt / (1 << 30), 3),
        "ms": round(dt * 1e3, 2),
        "shape": [m, cap, 16],
        "backend": jax.default_backend(),
    }


def bench_probe(n_dict: int = 1 << 20, n_query: int = 1 << 16):
    import jax

    from nydus_snapshotter_tpu.parallel import mesh as mesh_lib
    from nydus_snapshotter_tpu.parallel.sharded_dict import ShardedChunkDict

    rng = np.random.default_rng(2)
    dict_digests = rng.integers(0, 2**32, (n_dict, 8), dtype=np.uint32)
    queries = np.concatenate(
        [dict_digests[: n_query // 2], rng.integers(0, 2**32, (n_query - n_query // 2, 8), dtype=np.uint32)]
    )
    mesh = mesh_lib.make_mesh(len(jax.devices()))
    sd = ShardedChunkDict(dict_digests, mesh)

    rng2 = np.random.default_rng(3)
    queries2 = np.concatenate(
        [dict_digests[n_query // 2 : n_query], rng2.integers(0, 2**32, (n_query // 2, 8), dtype=np.uint32)]
    )
    dt, hits = timeit(sd.lookup_u32, (queries,), (queries2,))
    return {
        "stage": "dict-probe",
        "gibps": round(n_query * 32 / dt / (1 << 30), 3),
        "ms": round(dt * 1e3, 2),
        "shape": [n_dict, n_query],
        "backend": jax.default_backend(),
        "hit_rate": round(float(np.mean(np.asarray(hits) >= 0)), 3),
    }


def bench_host_fused(total_mib: int, chunk_kib: int = 64):
    """The native single-pass chunk+digest arm (no device, no jax init)."""
    import time as _time

    from nydus_snapshotter_tpu.ops import cdc, native_cdc

    if not native_cdc.chunk_digest_available():
        return {"stage": "host-fused", "error": "libchunk_engine.so unavailable"}
    rng = np.random.default_rng(4)
    # Full working set per pass (each rep processes ONE array), matching
    # the other stages' interpretation of --mib.
    arrs = [
        rng.integers(0, 256, total_mib << 20, dtype=np.uint8) for _ in range(2)
    ]
    p = cdc.CDCParams(chunk_kib << 10)
    best = float("inf")
    n_chunks = 0
    for rep in range(6):
        a = arrs[rep % 2]
        t = _time.perf_counter()
        cuts, _digests = native_cdc.chunk_digest_native(a, p)
        best = min(best, _time.perf_counter() - t)
        n_chunks = len(cuts)
    nbytes = arrs[0].nbytes
    return {
        "stage": "host-fused",
        "gibps": round(nbytes / best / (1 << 30), 3),
        "ms": round(best * 1e3, 2),
        "shape": [nbytes, n_chunks],
        "backend": "native",
    }


def _sha_pallas_ok() -> bool:
    from nydus_snapshotter_tpu.ops import sha256_pallas

    return sha256_pallas.supported(sha256_pallas.GROUP)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mib", type=int, default=256)
    ap.add_argument("--stage", default="all")
    args = ap.parse_args()

    if args.stage in ("all", "fused"):
        print(json.dumps(bench_host_fused(args.mib)), flush=True)
    if args.stage in ("all", "gear"):
        print(json.dumps(bench_gear(args.mib)), flush=True)
    if args.stage in ("all", "sha"):
        print(json.dumps(bench_sha(args.mib)), flush=True)
    if args.stage == "sha-pallas" or (args.stage == "all" and _sha_pallas_ok()):
        print(json.dumps(bench_sha_pallas(args.mib)), flush=True)
    if args.stage in ("all", "probe"):
        print(json.dumps(bench_probe()), flush=True)


if __name__ == "__main__":
    main()
