"""Chip smoke: the convert data plane on one TPU, through ``cmd.convert``.

    python chip_smoke.py            # one chip: pack/merge/check/unpack/dict
    python chip_smoke.py --chips 4  # only the sharded dict + sharded step

One process. Generates a node:21-shaped image from ``--seed`` (BASELINE
config 2: ~1 GiB of tar; in 4 layers, not 10+ — see SmokeConfig) and a
second image B sharing about half its files, packs them with
``--backend fused`` through
``nydus_snapshotter_tpu.cmd.convert.main`` exactly as the CLI does,
merges / checks / unpacks, and holds every artifact byte-identical to the
host reference (``--backend hybrid`` — the C++ lane shares no code with
the device kernels — for the image, ``--backend numpy`` for one layer).

Everything worth knowing is printed on earlier lines. The LAST line of
stdout is the verdict and nothing else:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Exits non-zero, without that line, when JAX finds no TPU or any phase
fails. Sets no JAX platform itself.
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import io
import json
import os
import shutil
import sys
import tarfile
import tempfile
import time
from dataclasses import dataclass

CHUNK_DEDUP = 0x10000  # the dedup-grade size bench.py uses
CHUNK_DEFAULT = 0x100000  # what a user who passes no --chunk-size compiles


class SmokeFailure(RuntimeError):
    pass


def need(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def verdict_line(platform: str, kind: str, count: int) -> str:
    """The contract's last line: exactly {ok, device{platform,kind,count}}."""
    return json.dumps(
        {"ok": True, "device": {"platform": platform, "kind": kind, "count": count}}
    )


@dataclass
class SmokeConfig:
    seed: int = 22
    image_mib: int = 1024
    image_b_mib: int = 512  # ~50% of its files are A's
    # CUTS. Every layer and every digester/chunk-size variant brings its
    # own _pass2 program, and the chip's compiler takes 53-115 s for one
    # (sha256) or ~240 s (blake3) — PERF.md. The script has 1200 s, cold.
    # In ISSUE 22's order: the numpy oracle on one layer (hybrid for the
    # rest); blake3 on ONE layer, deeper than the issue's three; the
    # default-chunk (1 MiB) pack on the smallest layer. Then the listed
    # last resort, fewer/larger layers: A's ~1 GiB in 4 layers instead
    # of 10+, B in 1, and the device probe lane with the kernel "auto"
    # selects only (no second program for the XLA gather). The sha256
    # fused pack of all of A is never cut, and the image is not smaller.
    weights: tuple = (384, 320, 192, 128)  # A's layers, MiB shares
    weights_b: tuple = (1,)
    blake3_layers: tuple = (2,)
    numpy_layers: tuple = (3,)
    default_chunk_layer: int = 3
    probe_layer_b: int = 0  # B layer whose files drive the device probe lane
    probe_kernels: tuple = ("auto",)
    require_tpu: bool = True  # False only in the CPU rehearsal (tests)

    def cuts(self) -> dict:
        return {
            "layers_a": len(self.weights),
            "layers_b": len(self.weights_b),
            "numpy_reference_layers": list(self.numpy_layers),
            "blake3_layers": list(self.blake3_layers),
            "default_chunk_layer": self.default_chunk_layer,
            "probe_kernels": list(self.probe_kernels),
            "hybrid_reference": "every layer",
            "never_cut": "sha256 fused pack of all of A",
        }


def _cli(argv: list[str]) -> dict:
    """cmd.convert.main(argv) in-process, as the CLI runs it; its one JSON
    line is captured (never reaches our stdout) and returned."""
    from nydus_snapshotter_tpu.cmd.convert import main as convert_main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = convert_main(argv)
    if rc != 0:
        raise SmokeFailure(f"cmd.convert {argv[:1]} exited {rc}: {argv}")
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def _sha(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while block := f.read(1 << 24):
            h.update(block)
    return h.hexdigest()


def _members(tar_bytes: bytes) -> dict[str, bytes]:
    out = {}
    with tarfile.open(fileobj=io.BytesIO(tar_bytes)) as tf:
        for m in tf:
            if m.isreg():
                out[m.name.lstrip("./")] = tf.extractfile(m).read()
    return out


def _programs() -> tuple[int, int]:
    from nydus_snapshotter_tpu.ops import fused_convert

    return fused_convert._pass1._cache_size(), fused_convert._pass2._cache_size()


def _counters() -> dict:
    from nydus_snapshotter_tpu.ops import fused_convert

    disp, by_bytes, stages, fallbacks = fused_convert._counters()
    out = {
        "dispatches": int(disp.value()),
        "bytes": int(by_bytes.value()),
        "host_fallbacks": int(fallbacks.value()),
    }
    for stage in ("layout", "h2d", "pass1_gear", "host_resolve", "pass2_digest"):
        out[f"{stage}_s"] = stages.value(stage)
    return out


def _pack(tar: str, out: str, backend: str, chunk: int, digester: str,
          chunk_dict: str = "") -> tuple[dict, dict]:
    """One `pack` through the CLI -> (its JSON line, what the call cost)."""
    argv = ["pack", "--in", tar, "--out", out, "--backend", backend,
            "--chunk-size", hex(chunk), "--digester", digester]
    if chunk_dict:
        argv += ["--chunk-dict", chunk_dict]
    p_before, c_before, t0 = _programs(), _counters(), time.perf_counter()
    res = _cli(argv)
    wall = time.perf_counter() - t0
    p_after, c_after = _programs(), _counters()
    cost = {
        "wall_s": wall,
        "new_pass1": p_after[0] - p_before[0],
        "new_pass2": p_after[1] - p_before[1],
    }
    for k, v in c_after.items():
        if k.endswith("_s"):
            cost[k] = v - c_before[k]
    return res, cost


def generate_images(cfg: SmokeConfig, log, image_b: bool = True) -> tuple[list[bytes], list[bytes]]:
    """Image A from the seed and (one chip only) image B, about half of
    whose files are A's."""
    import bench

    t0 = time.perf_counter()
    layers_a, info = bench.build_node_shaped_layers(
        cfg.image_mib, cfg.seed, weights=cfg.weights
    )
    facts = {"image_a": {**info, "mib": [len(t) / 2**20 for t in layers_a]}}
    layers_b = []
    if image_b:
        pool = [d for t in layers_a for d in _members(t).values()]
        layers_b, info = bench.build_node_shaped_layers(
            cfg.image_b_mib, cfg.seed + 1, pool=pool, reuse_fraction=0.5,
            weights=cfg.weights_b,
        )
        facts["image_b"] = {**info, "mib": [len(t) / 2**20 for t in layers_b]}
    log("corpus", seed=cfg.seed, gen_s=time.perf_counter() - t0, **facts)
    return layers_a, layers_b


def run_one_chip(work: str, cfg: SmokeConfig, log) -> None:
    """Every one-chip phase; raises SmokeFailure on the first mismatch."""
    import jax

    base_counters, base_programs = _counters(), _programs()
    log("cuts", **cfg.cuts())
    layers_a, layers_b = generate_images(cfg, log)
    os.makedirs(os.path.join(work, "blobs"))
    tars_a, tars_b = [], []
    for name, layers, tars in (("a", layers_a, tars_a), ("b", layers_b, tars_b)):
        for i, t in enumerate(layers):
            tars.append(os.path.join(work, f"{name}{i}.tar"))
            with open(tars[-1], "wb") as f:
                f.write(t)
    _phases(work, cfg, log, layers_a, layers_b, tars_a, tars_b)
    counters = {k: v - base_counters[k] for k, v in _counters().items()}
    p1, p2 = (now - was for now, was in zip(_programs(), base_programs))
    need(counters["host_fallbacks"] == 0, f"{counters['host_fallbacks']} fused batches fell back to the host")
    gear = _gear_kernel_in_program(layers_a)
    need(gear["tpu_custom_calls"] >= 1 or not cfg.require_tpu,
         "the compiled _pass1 holds no Pallas gear kernel")
    stats = jax.devices()[0].memory_stats() or {}
    log("summary", pass1_programs=p1, pass2_programs=p2, gear_kernel=gear,
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        bytes_limit=stats.get("bytes_limit"), **{f"ntpu_fused_convert_{k}": v for k, v in counters.items()})


def _phases(work, cfg, log, layers_a, layers_b, tars_a, tars_b) -> None:
    from nydus_snapshotter_tpu.converter.convert import blob_data_from_layer_blob

    base = _counters()["dispatches"]
    mib_a = [len(t) / 2**20 for t in layers_a]

    def first_call(label: dict, tar, out, chunk, digester, chunk_dict="") -> dict:
        res, cost = _pack(tar, out, "fused", chunk, digester, chunk_dict)
        log("pack", **label, digester=digester, chunk=hex(chunk), call="first", **cost)
        return res

    # -- A/sha256 through the fused lane: every layer (never cut) ------------
    results_a = [
        first_call({"image": "A", "layer": i, "mib": mib_a[i]}, tar,
                   f"{work}/a{i}.fused", CHUNK_DEDUP, "sha256")
        for i, tar in enumerate(tars_a)
    ]
    fused_packs = len(tars_a)
    # again, now that every program is compiled: compile vs steady walls
    for i, tar in enumerate(tars_a):
        res, cost = _pack(tar, f"{work}/a{i}.again", "fused", CHUNK_DEDUP, "sha256")
        fused_packs += 1
        need(cost["new_pass1"] == cost["new_pass2"] == 0,
             f"layer {i} recompiled on its second pack")
        need(res == results_a[i] and _sha(f"{work}/a{i}.again") == _sha(f"{work}/a{i}.fused"),
             f"layer {i}: second fused pack differs from the first")
        os.unlink(f"{work}/a{i}.again")
        log("pack", image="A", layer=i, digester="sha256", chunk=hex(CHUNK_DEDUP),
            mib=mib_a[i], call="steady", **cost)
    # -- reference for A/sha256: hybrid every layer, numpy on the cut list ---
    for i, tar in enumerate(tars_a):
        for backend in ["hybrid"] + (["numpy"] if i in cfg.numpy_layers else []):
            t0 = time.perf_counter()
            ref = _cli(["pack", "--in", tar, "--out", f"{work}/a{i}.{backend}",
                        "--backend", backend, "--chunk-size", hex(CHUNK_DEDUP)])
            need(ref == results_a[i], f"A layer {i}: fused result {results_a[i]} != {backend} {ref}")
            need(_sha(f"{work}/a{i}.{backend}") == _sha(f"{work}/a{i}.fused"),
                 f"A layer {i}: fused blob bytes != {backend}")
            log("reference", image="A", layer=i, backend=backend, digester="sha256",
                wall_s=time.perf_counter() - t0, identical=True)
    # -- blake3 and the default chunk size, on the cut lists ------------------
    variants = [(i, CHUNK_DEDUP, "blake3") for i in cfg.blake3_layers]
    variants.append((cfg.default_chunk_layer, CHUNK_DEFAULT, "sha256"))
    for i, chunk, digester in variants:
        tag = f"a{i}.{digester}.{chunk:x}"
        res = first_call({"image": "A", "layer": i, "mib": mib_a[i]}, tars_a[i],
                         f"{work}/{tag}.fused", chunk, digester)
        fused_packs += 1
        for backend in ["hybrid"] + (["numpy"] if i in cfg.numpy_layers else []):
            ref = _cli(["pack", "--in", tars_a[i], "--out", f"{work}/{tag}.{backend}",
                        "--backend", backend, "--chunk-size", hex(chunk),
                        "--digester", digester])
            need(ref == res and _sha(f"{work}/{tag}.{backend}") == _sha(f"{work}/{tag}.fused"),
                 f"A layer {i} {digester} chunk {chunk:#x}: fused != {backend}")
            log("reference", image="A", layer=i, backend=backend, digester=digester,
                chunk=hex(chunk), identical=True)

    # -- merge / check / unpack ----------------------------------------------
    boots = {}
    for arm in ("fused", "hybrid"):
        boots[arm] = f"{work}/A.{arm}.boot"
        merged = _cli(["merge", "--out", boots[arm],
                       *[f"{work}/a{i}.{arm}" for i in range(len(tars_a))]])
    need(_sha(boots["fused"]) == _sha(boots["hybrid"]), "merged bootstrap differs from reference")
    for i in range(len(tars_a)):
        os.unlink(f"{work}/a{i}.hybrid")
    checked = _cli(["check", "--boot", boots["fused"]])
    need(checked["blobs"] == merged["blob_digests"], "check lists other blobs than merge")
    for i in range(len(tars_a)):
        with open(f"{work}/a{i}.fused", "rb") as f:
            data = blob_data_from_layer_blob(f.read())
        with open(f"{work}/blobs/{results_a[i]['blob_id']}", "wb") as f:
            f.write(data)
    t0 = time.perf_counter()
    _cli(["unpack", "--boot", boots["fused"], "--blob-dir", f"{work}/blobs",
          "--out", f"{work}/A.unpacked.tar"])
    with open(f"{work}/A.unpacked.tar", "rb") as f:
        got = _members(f.read())
    os.unlink(f"{work}/A.unpacked.tar")
    want = {}
    for t in layers_a:
        want.update(_members(t))
    need(got.keys() == want.keys(), "unpacked tar has other members than the input")
    need(all(got[k] == want[k] for k in want), "an unpacked member differs from the input")
    log("merge_check_unpack", inodes=checked["inodes"], chunks=checked["chunks"],
        blobs=len(checked["blobs"]), members=len(want), unpack_s=time.perf_counter() - t0,
        bootstrap_identical=True, members_identical=True)

    # -- B against A's dict, and the device probe lane -----------------------
    # cmd.convert's --chunk-dict probes on the HOST (converter/stream
    # _process); pass 2's device probe lane is reached through
    # FusedDeviceEngine(chunk_dict=...), so the smoke drives that too.
    refs_fused = [
        first_call({"image": "B", "layer": i, "mib": len(layers_b[i]) / 2**20, "dict": "A"},
                   tar, f"{work}/b{i}.fused", CHUNK_DEDUP, "sha256", boots["fused"])
        for i, tar in enumerate(tars_b)
    ]
    for call in ("first", "steady"):
        _probe_lane(cfg, log, boots["fused"], layers_b[cfg.probe_layer_b], call)
    fused_packs += len(tars_b) + 2 * len(cfg.probe_kernels)
    refs_hybrid = [
        _cli(["pack", "--in", tar, "--out", f"{work}/b{i}.hybrid", "--backend", "hybrid",
              "--chunk-size", hex(CHUNK_DEDUP), "--chunk-dict", boots["fused"]])
        for i, tar in enumerate(tars_b)
    ]
    hits = {
        arm: _dedup_hits([f"{work}/b{i}.{arm}" for i in range(len(tars_b))],
                         {r["blob_id"] for r in refs})
        for arm, refs in (("fused", refs_fused), ("hybrid", refs_hybrid))
    }
    need(refs_fused == refs_hybrid and hits["fused"] == hits["hybrid"],
         "B vs dict: referenced blobs / dedup hits differ")
    need(all(_sha(f"{work}/b{i}.fused") == _sha(f"{work}/b{i}.hybrid")
             for i in range(len(tars_b))), "B vs dict: blob bytes differ")
    n_hit, n_all = hits["fused"]
    need(n_hit > 0 or not cfg.require_tpu, "B shares no chunk with A")
    log("dict_pack", layers=len(tars_b), chunks=n_all, dedup_hits=n_hit,
        referenced_blobs=sum(len(r["referenced_blobs"]) for r in refs_fused),
        identical=True)

    log("big_layer", ran=False, note="a layer near the 2 GiB addressing limit was not run")

    dispatched = _counters()["dispatches"] - base
    need(dispatched == fused_packs, f"fused dispatches {dispatched} != fused packs {fused_packs}")
    log("dispatches", fused_packs=fused_packs, ntpu_fused_convert_dispatches=dispatched)


def _dedup_hits(layer_blobs: list[str], own: set[str]) -> tuple[int, int]:
    """(chunks resolved into blobs of another image, all chunks)."""
    from nydus_snapshotter_tpu.converter.convert import bootstrap_from_layer_blob

    n_hit = n_all = 0
    for path in layer_blobs:
        with open(path, "rb") as f:
            bs = bootstrap_from_layer_blob(f.read())
        n_all += len(bs.chunks)
        n_hit += sum(bs.blobs[c.blob_index].blob_id not in own for c in bs.chunks)
    return n_hit, n_all


def _probe_lane(cfg, log, boot_a, layer_tar, call: str) -> None:
    """Every probe kernel once over one B layer's files against A's dict,
    held to the host-native probe. call: "first" (compiles) | "steady"."""
    import numpy as np

    from nydus_snapshotter_tpu.models.bootstrap import Bootstrap
    from nydus_snapshotter_tpu.ops import fused_convert
    from nydus_snapshotter_tpu.parallel import mesh as mesh_lib
    from nydus_snapshotter_tpu.parallel.sharded_dict import ShardedChunkDict

    with open(boot_a, "rb") as f:
        digests = [c.digest for c in Bootstrap.from_bytes(f.read()).chunks]
    # pass 2 emits sha256 states as big-endian words: key the dict likewise
    keys_u32 = np.frombuffer(b"".join(digests), dtype=">u4").astype(np.uint32).reshape(-1, 8)
    sdict = ShardedChunkDict(keys_u32, mesh_lib.make_mesh(1), probe_backend="host")
    keys, vals, depth, epoch = sdict.fused_probe_tables()
    streams = [np.frombuffer(d, dtype=np.uint8) for d in _members(layer_tar).values()]
    eng = fused_convert.FusedDeviceEngine(chunk_size=CHUNK_DEDUP)
    want = None
    for kernel in cfg.probe_kernels:
        before, t0 = _counters()["pass2_digest_s"], time.perf_counter()
        res = eng.process_many(streams, chunk_dict=(keys, vals), depth=depth,
                               probe_kernel=kernel, dict_epoch=epoch)
        wall = time.perf_counter() - t0
        if want is None:
            flat = [d for digs in res.digests for d in digs]
            q = np.frombuffer(b"".join(flat), dtype=">u4").astype(np.uint32).reshape(-1, 8)
            want = sdict.lookup_u32(q) + 1  # host-native probe; device value = index + 1
        need(np.array_equal(res.probe.astype(np.int64), want),
             f"device probe ({kernel}) disagrees with the host-native probe")
        need(kernel != "auto" or eng.probe_kernel_used == "pallas" or not cfg.require_tpu,
             "probe_kernel=auto did not take the Pallas probe on the chip")
        log("probe_lane", kernel=kernel, kernel_used=eng.probe_kernel_used, call=call,
            dict_entries=len(digests), table_slots=int(keys.shape[0]), depth=depth,
            queries=len(want), hits=int((want > 0).sum()), wall_s=wall,
            # gather + digest + probe
            pass2_digest_s=_counters()["pass2_digest_s"] - before, matches_host=True)
    need(int((want > 0).sum()) > 0 or not cfg.require_tpu, "the probed layer shares no chunk with A")


def _gear_kernel_in_program(layers_a) -> dict:
    """Lower _pass1 at the smallest layer's shape as the run did and
    count the Pallas kernels in the program text (the branch really
    taken at trace time, not what a supported() said)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nydus_snapshotter_tpu.ops import fused_convert

    eng = fused_convert.FusedDeviceEngine(chunk_size=CHUNK_DEDUP)
    n = min(len(t) for t in layers_a)
    buf, _ = eng.layout([np.zeros(n, np.uint8)])
    p = eng.params
    text = fused_convert._pass1.lower(
        jax.ShapeDtypeStruct(buf.shape, jnp.uint8), jnp.int32(n), p.mask_small,
        p.mask_large, fused_convert._wcap_for(n, p.bits + 2),
        fused_convert._wcap_for(n, p.bits - 2),
    ).as_text()
    return {"buffer_bytes": int(buf.size), "tpu_custom_calls": text.count("tpu_custom_call")}


def run_four_chips(cfg: SmokeConfig, log, dict_entries: int = 8 << 20,
                   n_queries: int = 1 << 16) -> None:
    """--chips 4: ONLY the mesh-sharded dict probe and the sharded convert
    step, each against its one-device / host comparison."""
    import jax
    import numpy as np

    import __graft_entry__ as graft
    from nydus_snapshotter_tpu.ops import fused_convert
    from nydus_snapshotter_tpu.parallel import mesh as mesh_lib
    from nydus_snapshotter_tpu.parallel.sharded_dict import ShardedChunkDict

    n = 4
    mesh = mesh_lib.make_mesh(n)
    rng = np.random.default_rng(cfg.seed)
    # -- registry-scale dict over the mesh vs the host-native probe -----------
    t0 = time.perf_counter()
    entries = rng.integers(0, 2**32, (dict_entries, 8), dtype=np.uint32)
    sdict = ShardedChunkDict(entries, mesh, probe_backend="device")
    build_s = time.perf_counter() - t0
    q = np.concatenate([entries[rng.integers(0, dict_entries, n_queries // 2)],
                        rng.integers(0, 2**32, (n_queries - n_queries // 2, 8), dtype=np.uint32)])
    rng.shuffle(q)
    t0 = time.perf_counter()
    got = sdict.lookup_u32(q)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    got2 = sdict.lookup_u32(q)
    steady_s = time.perf_counter() - t0
    sdict.probe_backend = "host"  # same tables through the native host probe
    want = sdict.lookup_u32(q)
    need(np.array_equal(got, want) and np.array_equal(got2, want),
         "mesh dict probe disagrees with the host-native probe")
    dkeys, _ = sdict._device_tables()
    shards = {s.device.id: int(s.data.nbytes) for s in dkeys.addressable_shards}
    need(len(shards) == n and len(set(shards.values())) == 1
         and sum(shards.values()) == dkeys.nbytes,
         f"dict table is not spread over {n} devices: {shards}")
    log("sharded_dict", entries=dict_entries, capacity=int(sdict.capacity), depth=int(sdict.max_depth),
        queries=n_queries, hits=int((want >= 0).sum()), build_s=build_s,
        probe_first_s=first_s, probe_steady_s=steady_s,
        table_bytes_per_device=shards, matches_host=True)

    # -- the sharded convert step vs the one-device fused engine ---------------
    layers_a, _ = generate_images(cfg, log, image_b=False)
    files = [d for t in layers_a for d in _members(t).values()]
    rep: dict = {}
    t0 = time.perf_counter()
    cuts4, digs4, boot4 = graft.sharded_convert_step(
        files, CHUNK_DEDUP, n, mesh, pack="extent", report=rep)
    step_s = time.perf_counter() - t0
    per_dev = rep["addressable_bytes_per_device"]
    need(len(per_dev) == n and rep["max_device_bytes"] <= rep["bound_bytes"],
         f"corpus bytes are not spread over {n} devices: {per_dev}")
    t0 = time.perf_counter()
    one = fused_convert.FusedDeviceEngine(chunk_size=CHUNK_DEDUP).process_many(files)
    one_s = time.perf_counter() - t0
    need(all(np.array_equal(a, b) for a, b in zip(cuts4, one.cuts)), "sharded cuts != one-device cuts")
    need(digs4 == one.digests, "sharded digests != one-device digests")
    need(boot4 == graft._emit_bootstrap(files, one.cuts, one.digests),
         "sharded bootstrap != one-device bootstrap")
    log("sharded_convert_step", files=len(files), corpus_bytes=rep["corpus_bytes"],
        chunks=sum(len(c) for c in cuts4), corpus_bytes_per_device=per_dev,
        bound_bytes=rep["bound_bytes"], buckets=rep["buckets"], first_call_wall_s=step_s,
        one_device_first_call_wall_s=one_s, identical=True)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()[:n]]
    log("summary", peak_bytes_in_use=peaks, **{f"ntpu_fused_convert_{k}": v for k, v in _counters().items()})


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=SmokeConfig.seed)
    args = ap.parse_args(argv)

    # Our stdout is for our lines alone: the real fd is kept aside, and
    # fd 1 / sys.stdout (make, libtpu, stray prints) go to stderr.
    out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr

    def log(phase: str, **facts) -> None:
        print(json.dumps({"phase": phase, **facts}, default=str), file=out, flush=True)

    t_start = time.perf_counter()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} ({dev.device_kind})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found {len(devices)} device(s)")

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    # Built from committed files only: no .so and no failure memo found
    # on disk is trusted.
    from nydus_snapshotter_tpu.utils import jax_cache, native_build

    native_build.rebuild_from_sources(sys.stderr)
    from nydus_snapshotter_tpu.ops import native_cdc

    if not native_cdc.available():
        sys.exit("chip_smoke: the native engine did not load after a clean build")
    cache = jax_cache.enable()

    def cached_programs() -> int:  # 0 at the start = a cold run
        return len(os.listdir(cache)) if os.path.isdir(cache) else 0

    log("start", platform=dev.platform, kind=dev.device_kind, devices=len(devices),
        chips=args.chips, jax=jax.__version__, compile_cache=cache,
        compile_cache_entries=cached_programs(), seed=args.seed)

    cfg = SmokeConfig(seed=args.seed)
    work = tempfile.mkdtemp(prefix="chip_smoke.", dir=os.environ.get("TMPDIR"))
    try:
        if args.chips == 4:
            run_four_chips(cfg, log)
        else:
            run_one_chip(work, cfg, log)
    except BaseException as e:
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
        shutil.rmtree(work, ignore_errors=True)
        raise SystemExit(1) from e
    shutil.rmtree(work, ignore_errors=True)
    log("done", wall_s=time.perf_counter() - t_start, compile_cache_entries=cached_programs())
    print(verdict_line(dev.platform, dev.device_kind, args.chips), file=out, flush=True)
    sys.exit(0)


if __name__ == "__main__":
    main()
