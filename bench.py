"""Conversion benchmark: full-path OCI→RAFS convert throughput per chip.

The headline ``value`` is what BASELINE.md actually targets — end-to-end
RAFS conversion (tar parse → CDC chunking → SHA-256 chunk digests → dedup →
lz4 compress → blob assembly + blob digest, `converter.convert.pack_layer`)
— over a node:21-shaped synthetic image: log-normal file sizes (thousands
of small files, a few big ones), a 40/40/20 text/binary/random
compressibility mix, and log-spread layer sizes (BASELINE configs #1-#3
without network access). The bare engine rate (chunk+digest only, the
number earlier rounds reported as the headline) is still measured and
reported under ``detail.engine_gibps``.

Engine selection is measured, not assumed (SURVEY §7 hard-part #3):

- **Boundaries**: the Pallas gear-bitmap kernel (ops/gear_pallas.py) when a
  TPU answers, else the native C++ fused arm / numpy windowed fallback.
- **Digests**: host (SHA-NI x3 batch scheduler) vs device (bucketed
  uint32-lane SHA-256) raced end-to-end on a calibration slice.
- **Dict probe**: native C++ open-addressing probe on a single chip (XLA
  TPU gathers are element-serial, measured ~1 µs/element), the sharded
  all_to_all path on multi-chip meshes.

Prints ONE JSON line: metric, value (GiB/s on this chip), unit, vs_baseline
(fraction of the 2.5 GiB/s per-chip share of the 20 GiB/s v5e-8 target),
plus engine/probe arms, the device JAX reports, and a full-path dict-dedup
run (image B converted against image A's chunk dict, measured dedup ratio).

One process holds the chip: every device arm runs in THIS process, and the
host-only profile children get JAX_PLATFORMS=cpu in their own env. A bench
that finds no TPU, or whose device arm raises, exits non-zero — there is no
host-arm fallback under a device name.
"""

from __future__ import annotations

import io
import json
import os
import sys
import tarfile
import time

import numpy as np

# A TARGET (north-star 20 GiB/s on a v5e-8), not a peak of any device.
PER_CHIP_TARGET_GIBPS = 20.0 / 8.0

CORPUS_MIB = int(os.environ.get("NTPU_BENCH_MIB", "384"))
IMAGE_MIB = int(os.environ.get("NTPU_BENCH_IMAGE_MIB", "192"))
CHUNK_SIZE = 0x10000  # 64 KiB average: matches dedup-grade chunking
N_FILES = 24
CALIBRATE_MIB = 16
REPS = 3


# ---------------------------------------------------------------------------
# Corpora
# ---------------------------------------------------------------------------


def build_corpus(total_mib: int, n_files: int) -> list[bytes]:
    """Flat corpus (uniform random blocks + exact duplicates) — feeds the
    bare-engine measurement and the engine race."""
    rng = np.random.default_rng(42)
    per = total_mib * (1 << 20) // n_files
    base = rng.integers(0, 256, per, dtype=np.uint8).tobytes()
    files = []
    for i in range(n_files):
        if i % 3 == 2:
            files.append(base)  # duplicated content: dedup work is real
        else:
            files.append(rng.integers(0, 256, per, dtype=np.uint8).tobytes())
    return files


_TEXT_BASE: np.ndarray | None = None


def _text_base(rng) -> np.ndarray:
    """1 MiB of word-like ASCII (compresses ~3-4x under lz4, like source
    trees / node_modules JS)."""
    global _TEXT_BASE
    if _TEXT_BASE is None:
        words = [
            rng.integers(97, 123, int(rng.integers(3, 11)), dtype=np.uint8)
            for _ in range(400)
        ]
        parts = []
        n = 0
        while n < (1 << 20):
            w = words[int(rng.integers(0, len(words)))]
            parts.append(w)
            parts.append(np.frombuffer(b" ", dtype=np.uint8))
            n += len(w) + 1
        _TEXT_BASE = np.concatenate(parts)[: 1 << 20]
    return _TEXT_BASE


def build_file_pool(total_mib: int, seed: int) -> list[bytes]:
    """Shared file pool: cross-image dedup in registries comes from the
    SAME files appearing in many images (base layers, npm packages), so
    the pool is whole files reused verbatim — offset-shifted byte ranges
    would defeat whole-file-sized CDC chunks and understate dedup."""
    rng = np.random.default_rng(seed)
    total = total_mib << 20
    files = []
    used = 0
    while used < total:
        size = int(np.clip(rng.lognormal(8.5, 2.0), 128, 8 << 20))
        r = rng.random()
        kind = "text" if r < 0.4 else ("binary" if r < 0.8 else "random")
        files.append(_gen_file(rng, size, kind))
        used += size
    return files


def _gen_file(rng, size: int, kind: str) -> bytes:
    if kind == "text":
        base = _text_base(rng)
        reps = -(-size // base.size)
        off = int(rng.integers(0, base.size))
        return np.concatenate([base[off:]] + [base] * reps)[:size].tobytes()
    if kind == "binary":
        # ELF-ish: random bytes with zero runs (compresses ~2x)
        data = rng.integers(0, 256, size, dtype=np.uint8)
        mask = rng.random(size) < 0.55
        data[mask] = 0
        return data.tobytes()
    return rng.integers(0, 256, size, dtype=np.uint8).tobytes()


def build_node_shaped_layers(
    total_mib: int,
    seed: int,
    pool: list[bytes] | None = None,
    reuse_fraction: float = 0.0,
    weights: tuple[float, ...] = (32.0, 16.0, 8.0, 4.0, 2.0, 2.0),
) -> tuple[list[bytes], dict]:
    """Synthetic image with a realistic shape: log-normal file sizes
    (median ~5 KiB, tail into MiBs — many small files like node:21's
    node_modules), 40/40/20 text/binary/random compressibility mix,
    log-spread layers by ``weights`` (default 6: one big rootfs layer,
    small app layers).

    ``pool``/``reuse_fraction``: that fraction of files takes its bytes
    from the shared content pool instead of fresh generation — the
    cross-image overlap that makes chunk-dict dedup hits real.
    """
    rng = np.random.default_rng(seed)
    total = total_mib << 20
    weights = np.asarray(weights)
    layer_bytes = (weights / weights.sum() * total).astype(np.int64)
    layers = []
    n_files = 0
    kind_bytes = {"text": 0, "binary": 0, "random": 0, "pooled": 0}
    for li, budget in enumerate(layer_bytes):
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w", format=tarfile.GNU_FORMAT) as tf:
            used = 0
            fi = 0
            while used < budget:
                use_pool = pool is not None and rng.random() < reuse_fraction
                if use_pool:
                    data = pool[int(rng.integers(0, len(pool)))]
                    kind_bytes["pooled"] += len(data)
                else:
                    size = int(np.clip(rng.lognormal(8.5, 2.0), 128, 8 << 20))
                    size = min(size, int(budget - used)) or 128
                    r = rng.random()
                    kind = (
                        "text" if r < 0.4 else ("binary" if r < 0.8 else "random")
                    )
                    data = _gen_file(rng, size, kind)
                    kind_bytes[kind] += size
                ti = tarfile.TarInfo(f"layer{li}/d{fi % 97}/f{fi}.bin")
                ti.size = len(data)
                tf.addfile(ti, io.BytesIO(data))
                used += len(data)
                fi += 1
                n_files += 1
        layers.append(buf.getvalue())
    info = {
        "files": n_files,
        "layers": len(layers),
        "mix_bytes_mib": {k: round(v / (1 << 20), 1) for k, v in kind_bytes.items()},
    }
    return layers, info


# ---------------------------------------------------------------------------
# Engine race (bare engine, calibration slice; every arm in this process)
# ---------------------------------------------------------------------------

# Candidate engine arms raced end-to-end (process_many on the calibration
# slice), all in this process: the chip belongs to one process at a time.
ENGINE_ARMS = {
    "host": {"backend": "hybrid"},
    "device_digest": {"backend": "hybrid", "digest_backend": "jax"},
    "device_all": {"backend": "jax", "digest_backend": "jax"},
    # full-path two-dispatch composition (ops/fused_convert): the whole
    # batch as one gear+compaction dispatch and one gather+digest dispatch
    "device_fused": {"backend": "fused"},
}


def _run_child_watchdog(argv: list[str], timeout: float):
    """Run a HOST-ONLY profile child under a hard timeout: the wait
    happens on a worker thread, so a child stuck in uninterruptible I/O
    can never stall the bench main thread. On timeout the child's whole
    process group is SIGKILLed and the reaper thread is abandoned
    (daemon) if even the reap hangs. The child's env pins
    JAX_PLATFORMS=cpu: this process holds the chip, and a child that
    reached for it would fail or hang.

    Returns ``(returncode, stdout, stderr)`` or ``None`` on timeout/spawn
    failure.
    """
    import signal
    import subprocess
    import threading

    try:
        proc = subprocess.Popen(
            argv,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            start_new_session=True,  # own pgid: killpg reaps grandchildren
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
    except OSError:
        return None
    result = {}

    def _wait():
        try:
            result["out"], result["err"] = proc.communicate()
        except Exception as e:  # noqa: BLE001 — watchdog must not raise
            result["exc"] = e

    waiter = threading.Thread(target=_wait, daemon=True)
    waiter.start()
    waiter.join(timeout)
    if waiter.is_alive() or "exc" in result:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError, OSError):
            pass
        waiter.join(5.0)  # give the reap a moment; abandon it if stuck
        return None
    return proc.returncode, result.get("out", ""), result.get("err", "")


def _time_engine(chunk_size: int, kwargs: dict, sample) -> float:
    from nydus_snapshotter_tpu.ops.chunker import ChunkDigestEngine

    eng = ChunkDigestEngine(chunk_size=chunk_size, mode="cdc", **kwargs)
    eng.process_many(sample)  # compile / thread-pool / build warm-up
    t = time.time()
    eng.process_many(sample)
    return time.time() - t


def calibrate_engine(chunk_size: int):
    """(winning arm name, timings) from the end-to-end race of every arm
    in ENGINE_ARMS on the calibration slice. A device arm that raises
    ends the bench: it does not "lose the race"."""
    rng = np.random.default_rng(7)
    sample = [rng.integers(0, 256, CALIBRATE_MIB << 19, dtype=np.uint8).tobytes()
              for _ in range(2)]
    times = {
        arm: _time_engine(chunk_size, kwargs, sample)
        for arm, kwargs in ENGINE_ARMS.items()
    }
    return min(times, key=times.get), {k: round(v, 3) for k, v in times.items()}


def build_probe(dict_digest_bytes: bytes):
    """(probe fn, arm name) for a chunk dict of raw 32-byte digests.

    Probe arm: native host table on one chip (device gathers are
    element-serial), sharded all_to_all on real meshes.
    """
    from nydus_snapshotter_tpu.parallel import mesh as mesh_lib
    from nydus_snapshotter_tpu.parallel.sharded_dict import ShardedChunkDict

    dict_digests = (
        np.frombuffer(dict_digest_bytes, dtype="<u4").reshape(-1, 8)
        if dict_digest_bytes
        else np.zeros((0, 8), np.uint32)
    )
    sdict = ShardedChunkDict(dict_digests, mesh_lib.make_mesh(1))
    sdict.lookup_digests([dict_digest_bytes[:32]] if dict_digest_bytes else [])
    return sdict.lookup_digests, (
        "host-native" if sdict._use_host_probe() else "device"
    )


def engine_flat_run(engine, probe) -> dict:
    """Bare-engine rate on the flat corpus (chunk+digest+probe only) —
    rounds 1-2's headline, kept for comparability."""
    files = build_corpus(CORPUS_MIB, N_FILES)
    total_bytes = sum(len(f) for f in files)
    best = None
    for _ in range(REPS):
        arrs = [np.frombuffer(f, dtype=np.uint8) for f in files]
        t0 = time.time()
        metas = engine.process_many(arrs)
        all_digests = [m.digest for f in metas for m in f]
        hits = np.asarray(probe(all_digests))
        elapsed = time.time() - t0
        n_hits = int(hits.sum() if hits.dtype == bool else (hits >= 0).sum())
        if best is None or elapsed < best[0]:
            best = (elapsed, len(all_digests), n_hits)
    return {
        "engine_gibps": round(total_bytes / best[0] / (1 << 30), 4),
        "corpus_mib": CORPUS_MIB,
        "n_chunks": best[1],
        "dict_hits": best[2],
    }


# ---------------------------------------------------------------------------
# Full-path conversion (the headline)
# ---------------------------------------------------------------------------


def _pack_kwargs(winner: str) -> dict:
    """PackOption fields matching the raced engine arm, so the headline
    full-path run actually uses the winning configuration."""
    if winner == "device_fused":
        return {"backend": "fused"}
    if winner == "device_all":
        return {"backend": "jax"}
    if winner == "device_digest":
        return {"backend": "hybrid", "digest_backend": "jax"}
    return {"backend": "hybrid"}


def _pack_layers(layers: list[bytes], opt, chunk_dict=None, stats=None) -> list:
    """Pack an image's layers in parallel (ordered results) — the
    reference's per-layer parallelism (one nydus-image process per layer);
    here the native engine, liblz4, and hashlib all drop the GIL, so
    threads scale on multi-core hosts and cost nothing on one core."""
    from concurrent.futures import ThreadPoolExecutor

    from nydus_snapshotter_tpu.converter.convert import pack_layer

    # Same auto-degradation as converter/stream._pack_threads: a pool on a
    # 1-core host measurably costs ~13% (GIL handoffs + contention) over
    # the serial walk it cannot beat.
    if len(layers) == 1 or (os.cpu_count() or 1) == 1:
        return [
            pack_layer(t, opt, chunk_dict=chunk_dict, stats=stats) for t in layers
        ]

    def _one(t):
        # Per-layer stats dict, merged after: the shared-dict accumulation
        # inside pack_stream is not thread-safe.
        st: dict = {}
        r = pack_layer(t, opt, chunk_dict=chunk_dict, stats=st)
        return r, st

    with ThreadPoolExecutor(max_workers=min(8, len(layers))) as pool:
        results = list(pool.map(_one, layers))
    if stats is not None:
        for _r, st in results:
            for k, v in st.items():
                stats[k] = stats.get(k, 0.0) + v
    return [r for r, _st in results]


def full_path_run(layers: list[bytes], opt) -> tuple[float, list, list, dict, dict]:
    """Best-of-REPS wall time converting every layer of the image; also
    returns a per-stage wall breakdown (scan / chunk_digest / dedup /
    assemble / bootstrap) measured on a SEPARATE layer-serial pass —
    parallel-layer stage clocks would sum thread wall time (including
    GIL/CPU contention) to more than the elapsed wall and mislead — plus
    a ``pipeline`` dict capturing the stage-parallel executor's overlap
    win (parallel vs serial wall, per-stage busy/utilization, worker
    counts and queue high-water) so the perf trajectory records it."""
    from nydus_snapshotter_tpu.converter.convert import pack_layer
    from nydus_snapshotter_tpu.converter.stream import _pack_threads
    from nydus_snapshotter_tpu.parallel import pipeline as pipeline_mod

    total = sum(len(t) for t in layers)
    best = None
    out = None
    snap_before = pipeline_mod.snapshot_counters()
    for _ in range(REPS):
        t0 = time.time()
        packed = _pack_layers(layers, opt)
        elapsed = time.time() - t0
        if best is None or elapsed < best:
            best = elapsed
            out = packed
    snap_after = pipeline_mod.snapshot_counters()
    stats: dict = {}
    t0 = time.time()
    for t in layers:
        pack_layer(t, opt, stats=stats)
    serial_wall = time.time() - t0
    blobs = [b for b, _ in out]
    results = [r for _, r in out]
    breakdown = {k: round(v, 4) for k, v in sorted(stats.items())}
    breakdown["serial_wall"] = round(serial_wall, 4)
    breakdown["parallel_wall"] = round(best, 4)

    n_threads = _pack_threads()
    pcfg = pipeline_mod.resolve_config(n_threads)
    runs = snap_after["runs"] - snap_before["runs"]
    stage_busy = {
        k: round((snap_after["stage_busy_s"][k] - snap_before["stage_busy_s"][k]) / REPS, 4)
        for k in snap_after["stage_busy_s"]
    }
    pipeline_info = {
        "enabled": pcfg.enabled,
        "engaged_runs": runs / REPS if runs else 0.0,
        "workers": {
            "pack_threads": n_threads,
            "chunk": pcfg.chunk_workers,
            "compress": pcfg.compress_workers,
        },
        "parallel_wall": round(best, 4),
        "serial_wall": round(serial_wall, 4),
        "speedup": round(serial_wall / max(1e-9, best), 4),
        # busy seconds per rep; utilization = busy / (wall × workers)
        "stage_busy_s": stage_busy,
        "stage_utilization": {
            "chunk": round(
                stage_busy.get("chunk", 0.0) / max(1e-9, best * pcfg.chunk_workers), 4
            ),
            "compress": round(
                stage_busy.get("compress", 0.0)
                / max(1e-9, best * pcfg.compress_workers),
                4,
            ),
        },
        "queue_high_water_bytes": snap_after["queue_high_water_bytes"],
        "shed_bytes": snap_after["shed_bytes"] - snap_before["shed_bytes"],
    }
    # Both lanes produce identical blobs; the headline is the best measured
    # full-path wall (the serial pass even carries stats overhead, so this
    # is conservative — it only de-noises, never flatters).
    best = min(best, serial_wall)
    return total / best / (1 << 30), blobs, results, breakdown, pipeline_info


def dedup_shaped_run(opt, pool: list[bytes]) -> dict:
    """Full-path BASELINE configs #2/#3: convert image A (all content from
    the shared pool), build its chunk dict from the merged bootstrap, then
    convert image B (~50% pool reuse) against the dict. Dedup ratio =
    bytes of B's chunks resolved to A's blobs / B's total chunk bytes."""
    from nydus_snapshotter_tpu.converter.convert import (
        Merge,
        bootstrap_from_layer_blob,
    )
    from nydus_snapshotter_tpu.converter.types import MergeOption
    from nydus_snapshotter_tpu.models.bootstrap import Bootstrap, ChunkDict

    layers_a, _ = build_node_shaped_layers(
        min(IMAGE_MIB, 128), seed=101, pool=pool, reuse_fraction=1.0
    )
    layers_b, _ = build_node_shaped_layers(
        min(IMAGE_MIB, 128), seed=202, pool=pool, reuse_fraction=0.5
    )

    t0 = time.time()
    packed_a = _pack_layers(layers_a, opt)
    t_a = time.time() - t0
    merged = Merge([b for b, _ in packed_a], MergeOption(with_tar=False))
    cdict = ChunkDict(Bootstrap.from_bytes(merged.bootstrap))

    t1 = time.time()
    packed_b = _pack_layers(layers_b, opt, chunk_dict=cdict)
    t_b = time.time() - t1

    own_ids = {r.blob_id for _, r in packed_b}
    dedup_bytes = 0
    total_chunk_bytes = 0
    for blob, _res in packed_b:
        bs = bootstrap_from_layer_blob(blob)
        for c in bs.chunks:
            total_chunk_bytes += c.uncompressed_size
            if bs.blobs[c.blob_index].blob_id not in own_ids:
                dedup_bytes += c.uncompressed_size
    bytes_a = sum(len(t) for t in layers_a)
    bytes_b = sum(len(t) for t in layers_b)
    return {
        "image_mib": round(bytes_a / (1 << 20)),
        "layers": len(layers_a),
        "dict_chunks": len(cdict),
        "build_dict_gibps": round(bytes_a / t_a / (1 << 30), 4),
        "convert_vs_dict_gibps": round(bytes_b / t_b / (1 << 30), 4),
        "dedup_ratio": round(dedup_bytes / max(1, total_chunk_bytes), 4),
    }


def _manifest_files(gen_of) -> list:
    """Materialize the committed REAL Ubuntu manifest as tar members.

    The manifest machinery (including the per-(path, generation) content
    synthesis) lives in scenario/corpus.py now, shared with the scenario
    engine's real-tree corpora so every real-layout consumer synthesizes
    the identical bytes.
    """
    from nydus_snapshotter_tpu.scenario import corpus as _corpus

    return _corpus.real_tree_members(gen_of=gen_of)


def _members_to_tar(members) -> bytes:
    from nydus_snapshotter_tpu.scenario import corpus as _corpus

    return _corpus.members_to_tar(members)


def real_image_run(opt) -> dict:
    """BASELINE configs #1/#2 on a REAL image shape (VERDICT r4 next #6).

    Image A = the real Ubuntu rootfs tree (single layer, as the real
    ubuntu base image ships). Its merged bootstrap is re-emitted in the
    REAL nydus v6 on-disk layout (models/nydus_real_write) and loaded
    back through the real-bootstrap parser as the chunk dict — the same
    round trip `--chunk-dict bootstrap=<real image>` takes. Image B = the
    upgraded rootfs (~25% of files changed) converted against that dict;
    the dedup ratio counts B's bytes resolved into A's blobs.
    """
    from nydus_snapshotter_tpu.converter.convert import (
        Merge,
        bootstrap_from_layer_blob,
        pack_layer,
    )
    from nydus_snapshotter_tpu.converter.types import MergeOption
    from nydus_snapshotter_tpu.models.bootstrap import Bootstrap, ChunkDict
    from nydus_snapshotter_tpu.models.nydus_real import load_any_bootstrap
    from nydus_snapshotter_tpu.models.nydus_real_write import (
        real_from_bootstrap,
        write_real_v6,
    )

    # RAFS v6's on-disk chunk index is a fixed grid, so REAL v6 images are
    # fixed-chunked (the nydus default; the fixture uses 1 MiB). Pack both
    # images fixed so the real-layout round trip is valid and B's chunk
    # digests can actually hit A's grid.
    from dataclasses import replace

    ropt = replace(opt, chunking="fixed")
    members_a = _manifest_files(lambda p: 0)
    tar_a = _members_to_tar(members_a)
    t0 = time.time()
    blob_a, res_a = pack_layer(tar_a, ropt)
    t_a = time.time() - t0
    merged = Merge([blob_a], MergeOption(with_tar=False))
    # real-layout round trip: our merged bootstrap -> REAL v6 bytes ->
    # real parser -> chunk dict (what the reference hands nydus-image)
    real_v6 = write_real_v6(
        real_from_bootstrap(Bootstrap.from_bytes(merged.bootstrap))
    )
    cdict = ChunkDict(load_any_bootstrap(real_v6))

    def gen_b(p):  # ~25% of files changed: an apt-upgrade-sized delta
        import hashlib as h

        return 1 if h.sha256(p.encode()).digest()[0] < 64 else 0

    tar_b = _members_to_tar(_manifest_files(gen_b))
    t1 = time.time()
    blob_b, res_b = pack_layer(tar_b, ropt, chunk_dict=cdict)
    t_b = time.time() - t1

    bs_b = bootstrap_from_layer_blob(blob_b)
    own = {res_b.blob_id}
    dedup_bytes = sum(
        c.uncompressed_size
        for c in bs_b.chunks
        if bs_b.blobs[c.blob_index].blob_id not in own
    )
    total_chunk_bytes = sum(c.uncompressed_size for c in bs_b.chunks)

    # VERDICT r5 #8: real-vs-real CROSS-TREE dedup — the second
    # real-derived tree (a sibling image: package subset + changed-file
    # delta, tools/extract_real_manifest.py --derive-tree2) converted
    # against tree1's real-bootstrap dict. The content-synthesis caveat
    # rides in the result: layout/chunk-grid is real, bytes are not.
    from nydus_snapshotter_tpu.scenario.corpus import cross_tree_dedup

    cross_tree = cross_tree_dedup(ropt)
    return {
        "source": "real ubuntu rootfs tree (committed manifest of the "
        "reference's v6 fixture; content synthesized per file)",
        "inodes": len(members_a),
        "image_mib": round(len(tar_a) / (1 << 20), 1),
        "convert_gibps": round(len(tar_a) / t_a / (1 << 30), 4),
        "dict_source": "REAL v6 layout round trip (write_real_v6 -> "
        "load_any_bootstrap)",
        "dict_chunks": len(cdict),
        "convert_vs_real_dict_gibps": round(len(tar_b) / t_b / (1 << 30), 4),
        "dedup_ratio": round(dedup_bytes / max(1, total_chunk_bytes), 4),
        "cross_tree_dedup": cross_tree,
    }


def stargz_zran_run(opt) -> dict:
    """BASELINE config #4 shape: eStargz index build + OCI-zran (targz-ref)
    conversion of a python:3.12-like compressible layer. Reports MiB/s of
    compressed input indexed (the blob itself is never re-stored)."""
    import gzip

    from nydus_snapshotter_tpu.converter.zran import pack_gzip_layer
    from nydus_snapshotter_tpu.stargz import index as stargz_index

    layers, _info = build_node_shaped_layers(min(IMAGE_MIB, 64), seed=404)
    raw = layers[0]
    raw_gz = gzip.compress(raw, compresslevel=6)

    t0 = time.time()
    bs = pack_gzip_layer(raw_gz, opt)
    t_zran = time.time() - t0

    # eStargz TOC -> bootstrap on the same content shape (the index path
    # the stargz resolver feeds; TOC synthesized from the layer listing,
    # using each member's real header offset as its stream offset so the
    # consecutive-offset deltas bootstrap_from_toc derives stay within the
    # blob).
    import hashlib

    entries = []
    with tarfile.open(fileobj=io.BytesIO(raw)) as tf:
        for m in tf.getmembers():
            if m.isreg():
                data = tf.extractfile(m).read()
                entries.append(
                    {
                        "name": m.name,
                        "type": "reg",
                        "size": m.size,
                        "offset": m.offset,
                        "digest": "sha256:" + hashlib.sha256(data).hexdigest(),
                    }
                )
    toc = {"version": 1, "entries": entries}
    t1 = time.time()
    toc_bs = stargz_index.bootstrap_from_toc(toc, blob_id="0" * 64)
    t_toc = time.time() - t1

    return {
        "layer_mib": round(len(raw) / (1 << 20), 1),
        "gzip_mib": round(len(raw_gz) / (1 << 20), 1),
        "zran_index_mibps": round(len(raw_gz) / (1 << 20) / t_zran, 1),
        "zran_chunks": len(bs.chunks),
        "estargz_toc_entries": len(entries),
        "toc_bootstrap_mibps": round(len(raw) / (1 << 20) / t_toc, 1),
    }


_LAZY_READ_CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
from tools.lazy_read_profile import profile
print(json.dumps(profile(mib=8, workers=4, latency_ms=2.0)))
"""


def lazy_read_run(repo: str, timeout: float = 240.0) -> dict:
    """Cold vs warm lazy-read profile (tools/lazy_read_profile.py) in a
    child under the hard watchdog: the fetch scheduler spins worker
    threads, and a wedged pool must cost the bench one timeout, not a
    hang. Returns the profile dict or a {'error': ...} marker."""
    res = _run_child_watchdog(
        [sys.executable, "-c", _LAZY_READ_CHILD.format(repo=repo)], timeout=timeout
    )
    if res is None:
        return {"error": f"lazy-read profile hung >{timeout:.0f}s (watchdog killed it)"}
    rc, stdout, stderr = res
    if rc != 0:
        tail = stderr.strip().splitlines()[-1] if stderr.strip() else ""
        return {"error": f"lazy-read profile exited rc={rc}: {tail}"[:200]}
    try:
        return json.loads(stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": "lazy-read profile produced no JSON"}


_SNAPSHOT_OPS_CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
from tools.snapshot_profile import profile
print(json.dumps(profile(layers=8, pods=8)))
"""


def snapshot_ops_run(repo: str, timeout: float = 240.0) -> dict:
    """Snapshot control-plane storm (tools/snapshot_profile.py) in a child
    under the hard watchdog: serial vs concurrent wall plus p50/p99 per
    op, with the identity gate evaluated in-process. A wedged prepare
    board or usage accountant costs one timeout, not a hang."""
    res = _run_child_watchdog(
        [sys.executable, "-c", _SNAPSHOT_OPS_CHILD.format(repo=repo)], timeout=timeout
    )
    if res is None:
        return {"error": f"snapshot profile hung >{timeout:.0f}s (watchdog killed it)"}
    rc, stdout, stderr = res
    if rc != 0:
        tail = stderr.strip().splitlines()[-1] if stderr.strip() else ""
        return {"error": f"snapshot profile exited rc={rc}: {tail}"[:200]}
    try:
        return json.loads(stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": "snapshot profile produced no JSON"}


_TRACE_CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
from tools.trace_profile import profile
print(json.dumps(profile(layers=4, pods=4, reps=2)))
"""


def trace_run(repo: str, timeout: float = 240.0) -> dict:
    """Trace overhead profile (tools/trace_profile.py) in a child under
    the hard watchdog: enabled-vs-disabled storm overhead, spans/sec into
    the ring, drops, and the end-to-end Prepare tree gate."""
    res = _run_child_watchdog(
        [sys.executable, "-c", _TRACE_CHILD.format(repo=repo)], timeout=timeout
    )
    if res is None:
        return {"error": f"trace profile hung >{timeout:.0f}s (watchdog killed it)"}
    rc, stdout, stderr = res
    if rc != 0:
        tail = stderr.strip().splitlines()[-1] if stderr.strip() else ""
        return {"error": f"trace profile exited rc={rc}: {tail}"[:200]}
    try:
        return json.loads(stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": "trace profile produced no JSON"}


_CHUNK_DICT_CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
from tools.chunk_dict_profile import profile
print(json.dumps(profile(entries_m=2.0, grow_k=200)))
"""


_PEER_STORM_CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
from tools.cluster_storm_profile import profile
print(json.dumps(profile(pods=8, mib=1, reps=2)))
"""


def peer_storm_run(repo: str, timeout: float = 240.0) -> dict:
    """Cluster deploy-storm profile (tools/cluster_storm_profile.py) in
    a child under the hard watchdog: registry egress ratio (peers on vs
    off), aggregate storm wall + paired best-rep/analytic speedup, and
    the weighted-tenant fairness spread. Dozens of UDS servers and fetch
    pools spin up — a wedge must cost one timeout, not a hang."""
    res = _run_child_watchdog(
        [sys.executable, "-c", _PEER_STORM_CHILD.format(repo=repo)], timeout=timeout
    )
    if res is None:
        return {"error": f"peer storm hung >{timeout:.0f}s (watchdog killed it)"}
    rc, stdout, stderr = res
    if rc != 0:
        tail = stderr.strip().splitlines()[-1] if stderr.strip() else ""
        return {"error": f"peer storm exited rc={rc}: {tail}"[:200]}
    try:
        return json.loads(stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": "peer storm produced no JSON"}


_PEER_TOPOLOGY_CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
from tools.cluster_storm_profile import topology_profile
print(json.dumps(topology_profile(pods=6, mib=2, reps=1)))
"""


def peer_topology_run(repo: str, timeout: float = 300.0) -> dict:
    """Hierarchical rack/zone/region topology profile (the ISSUE 18
    `--topology` arm of tools/cluster_storm_profile.py) in a child under
    the hard watchdog: per-zone origin-egress ratio vs unique bytes,
    hedged-vs-unhedged slow-peer p99 (paired best-rep), and the
    kill-a-zone identity arm. A 3-rack x 2-zone mesh of UDS servers
    spins up — a wedge must cost one timeout, not a hang."""
    res = _run_child_watchdog(
        [sys.executable, "-c", _PEER_TOPOLOGY_CHILD.format(repo=repo)],
        timeout=timeout,
    )
    if res is None:
        return {"error": f"peer topology hung >{timeout:.0f}s (watchdog killed it)"}
    rc, stdout, stderr = res
    if rc != 0:
        tail = stderr.strip().splitlines()[-1] if stderr.strip() else ""
        return {"error": f"peer topology exited rc={rc}: {tail}"[:200]}
    try:
        return json.loads(stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": "peer topology produced no JSON"}


_SOCI_CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
from tools.soci_profile import profile
print(json.dumps(profile(pods=4, mib=4, reps=2)))
"""


def soci_run(repo: str, timeout: float = 300.0) -> dict:
    """Seekable-OCI profile (tools/soci_profile.py) in a child under the
    hard watchdog: index build MiB/s vs the banked stargz_zran line,
    cold first-file-read latency curve vs full pull, and the mini
    indexed-storm origin-egress ratio on unconverted images. Peer UDS
    servers and fetch pools spin up — a wedge costs one timeout."""
    res = _run_child_watchdog(
        [sys.executable, "-c", _SOCI_CHILD.format(repo=repo)], timeout=timeout
    )
    if res is None:
        return {"error": f"soci profile hung >{timeout:.0f}s (watchdog killed it)"}
    rc, stdout, stderr = res
    if rc != 0:
        tail = stderr.strip().splitlines()[-1] if stderr.strip() else ""
        return {"error": f"soci profile exited rc={rc}: {tail}"[:200]}
    try:
        return json.loads(stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": "soci profile produced no JSON"}


_SOCI_FORMATS_CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
from tools.soci_profile import formats_profile
print(json.dumps(formats_profile(pods=4, mib=4, reps=2)))
"""


def soci_formats_run(repo: str, timeout: float = 300.0) -> dict:
    """Universal lazy-format matrix (tools/soci_profile.py --formats) in
    a child under the hard watchdog: per-format byte identity, cold
    first-read ratios (zstd >= 5x), FormatRouter routing, and the
    mini mixed-format storm (TOC adoption at ~zero prepare bytes,
    egress <= 1.05x unique compressed bytes)."""
    res = _run_child_watchdog(
        [sys.executable, "-c", _SOCI_FORMATS_CHILD.format(repo=repo)],
        timeout=timeout,
    )
    if res is None:
        return {"error": f"soci formats hung >{timeout:.0f}s (watchdog killed it)"}
    rc, stdout, stderr = res
    if rc != 0:
        tail = stderr.strip().splitlines()[-1] if stderr.strip() else ""
        return {"error": f"soci formats exited rc={rc}: {tail}"[:200]}
    try:
        return json.loads(stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": "soci formats produced no JSON"}


_FLEET_OBS_CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
from tools.fleet_obs_profile import profile
print(json.dumps(profile(layers=4, pods=4, reps=2)))
"""


def fleet_obs_run(repo: str, timeout: float = 240.0) -> dict:
    """Fleet observability profile (tools/fleet_obs_profile.py) in a
    child under the hard watchdog: federation scrape + trace aggregation
    overhead on a snapshot storm (paired best-rep + duty-cycle bound)
    plus the spawned-member ntpuctl smoke. Two daemon subprocesses and a
    controller spin up — a wedge must cost one timeout, not a hang."""
    res = _run_child_watchdog(
        [sys.executable, "-c", _FLEET_OBS_CHILD.format(repo=repo)], timeout=timeout
    )
    if res is None:
        return {"error": f"fleet obs profile hung >{timeout:.0f}s (watchdog killed it)"}
    rc, stdout, stderr = res
    if rc != 0:
        tail = stderr.strip().splitlines()[-1] if stderr.strip() else ""
        return {"error": f"fleet obs profile exited rc={rc}: {tail}"[:200]}
    try:
        return json.loads(stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": "fleet obs profile produced no JSON"}


def chunk_dict_run(repo: str, timeout: float = 240.0) -> dict:
    """Chunk-dict growth + service profile (tools/chunk_dict_profile.py)
    in a child under the hard watchdog: incremental-vs-rebuild best-rep
    ratio, identity gates, and the DictService round-trip byte-identity.
    A wedged UDS server costs one timeout, not a hang."""
    res = _run_child_watchdog(
        [sys.executable, "-c", _CHUNK_DICT_CHILD.format(repo=repo)], timeout=timeout
    )
    if res is None:
        return {"error": f"chunk-dict profile hung >{timeout:.0f}s (watchdog killed it)"}
    rc, stdout, stderr = res
    if rc != 0:
        tail = stderr.strip().splitlines()[-1] if stderr.strip() else ""
        return {"error": f"chunk-dict profile exited rc={rc}: {tail}"[:200]}
    try:
        return json.loads(stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": "chunk-dict profile produced no JSON"}


_DICT_HA_CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
from tools.dict_ha_profile import profile
print(json.dumps(profile(images=6, files=4, reps=2)))
"""


def dict_ha_run(repo: str, timeout: float = 420.0) -> dict:
    """Dict-shard HA profile (tools/dict_ha_profile.py) in a child under
    the hard watchdog: the 2-shard/1-replica kill-the-primary storm —
    converter byte-identity across a SIGKILL, automatic promotion,
    budget-bounded replica catch-up, and the paired best-rep demand-p95
    gate. Spawns a controller + 4 member processes; a wedge costs one
    timeout, not a hang."""
    res = _run_child_watchdog(
        [sys.executable, "-c", _DICT_HA_CHILD.format(repo=repo)], timeout=timeout
    )
    if res is None:
        return {"error": f"dict-ha profile hung >{timeout:.0f}s (watchdog killed it)"}
    rc, stdout, stderr = res
    if rc != 0:
        tail = stderr.strip().splitlines()[-1] if stderr.strip() else ""
        return {"error": f"dict-ha profile exited rc={rc}: {tail}"[:200]}
    try:
        return json.loads(stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": "dict-ha profile produced no JSON"}


_SOAK_CHILD = """
import json, os, sys
sys.path.insert(0, {repo!r})
from tools.soak_profile import profile
spec = os.path.join({repo!r}, "misc", "scenarios", "soak_smoke.toml")
print(json.dumps(profile(spec, mini=True)))
"""


def soak_run(repo: str, timeout: float = 420.0) -> dict:
    """Mini endurance soak (tools/soak_profile.py --mini over
    soak_smoke.toml) in a child under the hard watchdog: 3 seeded
    arrival epochs with corpus drift, per-epoch audit + leak sentinels,
    one scale-up cycle and serial spot-epoch identity. A wedged epoch
    costs one timeout, not a hang."""
    res = _run_child_watchdog(
        [sys.executable, "-c", _SOAK_CHILD.format(repo=repo)], timeout=timeout
    )
    if res is None:
        return {"error": f"soak profile hung >{timeout:.0f}s (watchdog killed it)"}
    rc, stdout, stderr = res
    if rc != 0:
        tail = stderr.strip().splitlines()[-1] if stderr.strip() else ""
        return {"error": f"soak profile exited rc={rc}: {tail}"[:200]}
    try:
        return json.loads(stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": "soak profile produced no JSON"}


_COMPRESSION_CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
from tools.compression_profile import profile
print(json.dumps(profile(mib=12, reps=2)))
"""


def compression_adaptive_run(repo: str, timeout: float = 240.0) -> dict:
    """Adaptive-codec profile (tools/compression_profile.py) in a child
    under the hard watchdog: paired best-rep + analytic speedup at
    reference defaults, roundtrip identity on every arm, bypass
    discipline, trained-dict loud-failure and DCtx-pool gates. A wedged
    codec costs one timeout, not a hang."""
    res = _run_child_watchdog(
        [sys.executable, "-c", _COMPRESSION_CHILD.format(repo=repo)], timeout=timeout
    )
    if res is None:
        return {"error": f"compression profile hung >{timeout:.0f}s (watchdog killed it)"}
    rc, stdout, stderr = res
    if rc != 0:
        tail = stderr.strip().splitlines()[-1] if stderr.strip() else ""
        return {"error": f"compression profile exited rc={rc}: {tail}"[:200]}
    try:
        return json.loads(stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": "compression profile produced no JSON"}


_VECTORIZED_CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
from tools.compression_profile import batched_profile, vectorized_profile
out = {{}}
try:
    out["scan"] = vectorized_profile(mib=12, reps=2)
except Exception as e:
    out["scan"] = {{"error": str(e)[:200]}}
try:
    out["batch"] = batched_profile(mib=12, reps=3)
except Exception as e:
    out["batch"] = {{"error": str(e)[:200]}}
print(json.dumps(out))
"""


def compression_vectorized_run(repo: str, timeout: float = 240.0) -> dict:
    """Vectorized-scan + batched-lane gates (tools/compression_profile.py
    --vectorized --batched) in a watchdogged child: cut/frame identity
    aborts inside the child, so a diverging kernel surfaces as an error
    row here instead of silently banking a wrong-output speedup."""
    res = _run_child_watchdog(
        [sys.executable, "-c", _VECTORIZED_CHILD.format(repo=repo)],
        timeout=timeout,
    )
    if res is None:
        return {"error": f"vectorized profile hung >{timeout:.0f}s (watchdog killed it)"}
    rc, stdout, stderr = res
    if rc != 0:
        tail = stderr.strip().splitlines()[-1] if stderr.strip() else ""
        return {"error": f"vectorized profile exited rc={rc}: {tail}"[:200]}
    try:
        return json.loads(stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": "vectorized profile produced no JSON"}


def main() -> None:
    repo = os.path.dirname(os.path.abspath(__file__))

    from nydus_snapshotter_tpu.converter.types import PackOption
    from nydus_snapshotter_tpu.ops import native_cdc
    from nydus_snapshotter_tpu.ops.chunker import ChunkDigestEngine
    from nydus_snapshotter_tpu.utils import jax_cache

    jax_cache.enable()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(
            f"bench: needs a TPU, JAX found {dev.platform!r} ({dev.device_kind}); "
            "a host-arm number is not reported under a device name"
        )
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    winner, cal = calibrate_engine(CHUNK_SIZE)

    bench_engine = ChunkDigestEngine(
        chunk_size=CHUNK_SIZE, mode="cdc", **ENGINE_ARMS[winner]
    )
    fused = bench_engine._fused_available()
    if bench_engine.backend == "jax":
        from nydus_snapshotter_tpu.ops import gear_pallas

        gear_kernel = "pallas" if gear_pallas.supported(bench_engine.window) else "xla"
    elif fused:
        gear_kernel = "host-fused"
    elif native_cdc.available():
        gear_kernel = "host-native"
    else:
        gear_kernel = "host-numpy"

    # Probe warm-up dict (also forces compilation of probe shapes).
    warm_metas = bench_engine.process_many(build_corpus(CALIBRATE_MIB, 2))
    warm_digest_bytes = b"".join(m.digest for metas in warm_metas for m in metas)
    probe, probe_arm = build_probe(warm_digest_bytes)
    if winner != "host":
        bench_engine.process_many(build_corpus(CORPUS_MIB, N_FILES))  # shapes

    # ---- headline: full-path convert of the node-shaped image ----
    opt = PackOption(chunk_size=CHUNK_SIZE, chunking="cdc", **_pack_kwargs(winner))
    layers, corpus_info = build_node_shaped_layers(IMAGE_MIB, seed=7)
    full_gibps, blobs, results, stage_breakdown, pipeline_info = full_path_run(
        layers, opt
    )
    comp_bytes = sum(r.blob_size for r in results)
    corpus_info["compress_ratio"] = round(
        comp_bytes / max(1, sum(len(t) for t in layers)), 4
    )

    # Speed-profile arm: same full path with the documented lz4
    # acceleration dial (PackOption.lz4_acceleration=8). The headline
    # stays at fidelity defaults; this records what the knob buys and
    # what ratio it costs on the same corpus.
    opt_accel = PackOption(
        chunk_size=CHUNK_SIZE, chunking="cdc", lz4_acceleration=8,
        **_pack_kwargs(winner),
    )
    total_in = sum(len(t) for t in layers)
    accel_best = None
    packed_accel = None
    for _ in range(REPS):  # same best-of-REPS discipline as the headline
        t0 = time.time()
        packed_accel = _pack_layers(layers, opt_accel)
        dt = time.time() - t0
        accel_best = dt if accel_best is None or dt < accel_best else accel_best
    accel_profile = {
        "lz4_acceleration": 8,
        "full_path_gibps": round(total_in / accel_best / (1 << 30), 4),
        "compress_ratio": round(
            sum(r.blob_size for _b, r in packed_accel) / max(1, total_in), 4
        ),
    }

    # zstd arm: same full path at the reference toolchain's modern default
    # compressor (native fused section assembly via the system libzstd,
    # level constants.ZSTD_LEVEL) — records the speed/ratio tradeoff vs
    # the lz4 headline on the same corpus.
    opt_zstd = PackOption(
        chunk_size=CHUNK_SIZE, chunking="cdc", compressor="zstd",
        **_pack_kwargs(winner),
    )
    zstd_best = None
    packed_zstd = None
    for _ in range(REPS):
        t0 = time.time()
        packed_zstd = _pack_layers(layers, opt_zstd)
        dt = time.time() - t0
        zstd_best = dt if zstd_best is None or dt < zstd_best else zstd_best
    from nydus_snapshotter_tpu import constants as _const

    zstd_profile = {
        "level": _const.ZSTD_LEVEL,
        "full_path_gibps": round(total_in / zstd_best / (1 << 30), 4),
        "compress_ratio": round(
            sum(r.blob_size for _b, r in packed_zstd) / max(1, total_in), 4
        ),
    }

    # Reference-defaults arm: the real nydus-image defaults are blake3
    # chunk digests + zstd — the configuration whose output interops with
    # real nydus images (chunk-dict content hits are digest-keyed). The
    # blake3 digests ride the same fused native pass (8-way AVX2 leaves).
    opt_refdef = PackOption(
        chunk_size=CHUNK_SIZE, chunking="cdc", compressor="zstd",
        digester="blake3", **_pack_kwargs(winner),
    )
    refdef_best = None
    packed_refdef = None
    for _ in range(REPS):
        t0 = time.time()
        packed_refdef = _pack_layers(layers, opt_refdef)
        dt = time.time() - t0
        refdef_best = dt if refdef_best is None or dt < refdef_best else refdef_best
    reference_defaults_profile = {
        "digester": "blake3",
        "compressor": "zstd",
        "full_path_gibps": round(total_in / refdef_best / (1 << 30), 4),
        "compress_ratio": round(
            sum(r.blob_size for _b, r in packed_refdef) / max(1, total_in), 4
        ),
    }

    # Uncompressed arm + derived codec economics: the denominator for the
    # compression scaling argument (docs/COMPRESSION_SCALING.md). The
    # per-core codec rate is derived from the measured wall deltas on the
    # ACTUAL corpus (unique post-dedup bytes / extra wall vs "none"), so
    # each round re-grounds the cores-needed-for-20GiB/s table on the
    # bench box rather than trusting the doc's frozen numbers.
    opt_none = PackOption(
        chunk_size=CHUNK_SIZE, chunking="cdc", compressor="none",
        **_pack_kwargs(winner),
    )
    none_best = None
    packed_none = None
    for _ in range(REPS):
        t0 = time.time()
        packed_none = _pack_layers(layers, opt_none)
        dt = time.time() - t0
        none_best = dt if none_best is None or dt < none_best else none_best
    uniq_bytes = sum(r.blob_size for _b, r in packed_none)  # raw unique
    ncores = os.cpu_count() or 1

    # Per-core codec rates need SERIAL walls: _pack_layers runs layers on
    # a thread pool, so on a multi-core box its wall deltas would reflect
    # N cores compressing concurrently and overstate the per-core rate.
    def _serial_wall(o):
        best = None
        for _ in range(REPS):
            t0 = time.time()
            for t in layers:
                pack_layer_fn(t, o)
            dt = time.time() - t0
            best = dt if best is None or dt < best else best
        return best

    from nydus_snapshotter_tpu.converter.convert import (
        pack_layer as pack_layer_fn,
    )

    none_serial = _serial_wall(opt_none)
    lz4_serial = _serial_wall(opt)
    zstd_serial = _serial_wall(opt_zstd)

    def _codec_rate(wall):
        # unique bytes compressed during (wall - uncompressed wall);
        # None when the delta is within noise (a codec wall at or below
        # the uncompressed wall) rather than an absurd clamped rate
        extra = wall - none_serial
        if extra <= 0.01 * none_serial:
            return None
        return uniq_bytes / extra / (1 << 30)

    target = PER_CHIP_TARGET_GIBPS * 8  # 20 GiB/s aggregate
    uniq_frac = uniq_bytes / max(1, total_in)
    lz4_rate = _codec_rate(lz4_serial)
    zstd_rate = _codec_rate(zstd_serial)
    compression_economics = {
        "uncompressed_full_path_gibps": round(
            total_in / none_best / (1 << 30), 4
        ),
        "unique_fraction_post_dedup": round(uniq_frac, 4),
        "lz4_gibps_per_core": round(lz4_rate, 4) if lz4_rate else None,
        "zstd_gibps_per_core": round(zstd_rate, 4) if zstd_rate else None,
        "cores_for_20gibps_lz4": (
            round(target * uniq_frac / lz4_rate, 1) if lz4_rate else None
        ),
        "cores_for_20gibps_zstd": (
            round(target * uniq_frac / zstd_rate, 1) if zstd_rate else None
        ),
        "refdef_vs_uncompressed": round(
            reference_defaults_profile["full_path_gibps"]
            / max(1e-9, total_in / none_best / (1 << 30)),
            4,
        ),
        "overlap_note": (
            "per-chunk frames are independent; compression scales across "
            f"cores and pipelines behind chunk+digest — this box has "
            f"{ncores} core(s), so walls here are fully serialized"
        ),
    }

    # ---- detail runs ----
    engine_detail = engine_flat_run(bench_engine, probe)
    pool = build_file_pool(min(IMAGE_MIB, 128), seed=555)
    shaped = dedup_shaped_run(opt, pool)
    stargz_zran = stargz_zran_run(opt)
    real_image = real_image_run(opt)
    lazy_read = lazy_read_run(repo)
    snapshot_ops = snapshot_ops_run(repo)
    trace_detail = trace_run(repo)
    chunk_dict_detail = chunk_dict_run(repo)
    dict_ha_detail = dict_ha_run(repo)
    soak_detail = soak_run(repo)
    peer_storm = peer_storm_run(repo)
    peer_topology = peer_topology_run(repo)
    fleet_obs = fleet_obs_run(repo)
    soci_detail = soci_run(repo)
    soci_detail["formats"] = soci_formats_run(repo)
    # Adaptive-codec engine numbers ride under detail.compression next
    # to the per-codec economics they change.
    compression_economics["adaptive"] = compression_adaptive_run(repo)
    # Vectorized scan + batched codec lane: identity-gated best-rep
    # ratios and ns/byte bounds for the two compression-wall kernels.
    compression_economics["vectorized"] = compression_vectorized_run(repo)

    print(
        json.dumps(
            {
                # the arm that produced it is detail.engine_arm
                "metric": "rafs_convert_full_path_per_chip",
                "value": round(full_gibps, 4),
                "unit": "GiB/s",
                "vs_baseline": round(full_gibps / PER_CHIP_TARGET_GIBPS, 4),
                "detail": {
                    "metric_note": (
                        "headline switched r3 from bare engine to FULL-PATH "
                        "convert (VERDICT r2 next #2); engine_flat.engine_gibps "
                        "is the series comparable to r1/r2 values"
                    ),
                    "image_mib": IMAGE_MIB,
                    "chunk_size": CHUNK_SIZE,
                    "compressor": opt.compressor,
                    "corpus": corpus_info,
                    "engine_arm": winner,
                    "digest_backend": opt.digest_backend
                    or bench_engine.digest_backend,
                    "gear_kernel": gear_kernel,
                    "probe_arm": probe_arm,
                    "device": device,
                    "calibration": cal,
                    "engine_flat": engine_detail,
                    "stage_breakdown_s": stage_breakdown,
                    "pipeline": pipeline_info,
                    "lazy_read": lazy_read,
                    "snapshot_ops": snapshot_ops,
                    "trace": trace_detail,
                    "chunk_dict": chunk_dict_detail,
                    "dict_ha": dict_ha_detail,
                    "soak": soak_detail,
                    "peer_storm": peer_storm,
                    "peer_topology": peer_topology,
                    "fleet_obs": fleet_obs,
                    "soci": soci_detail,
                    "accel_profile": accel_profile,
                    "zstd_profile": zstd_profile,
                    "reference_defaults_profile": reference_defaults_profile,
                    "compression": compression_economics,
                    "baseline_shaped": shaped,
                    "real_image": real_image,
                    "stargz_zran": stargz_zran,
                    "host_cores": os.cpu_count(),
                },
            }
        )
    )


if __name__ == "__main__":
    sys.path.insert(0, ".")
    main()
