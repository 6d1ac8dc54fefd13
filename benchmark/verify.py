"""The comparison that decides ``correct``, made after the window on what the
window itself wrote. Every number has its limit beside it; all are exact.

The decider is ``benchmark/reference.py`` (imports nothing of the program,
takes nothing it has made), on the arms the configuration's ``pack_args``
state (``arms``; where a flag is absent, the CLI's default):

* ``--chunking`` ``cdc`` | ``fixed`` and ``--chunk-size`` choose the cut rule,
  ``--digester`` ``sha256`` | ``blake3`` the digest: it cuts and digests a
  seed-drawn sample of the tars' files, the largest among them, and the chunk
  records of the kept artifacts must say the same (``plain_files_differ``);
* ``--compressor`` ``lz4_block`` | ``zstd`` | ``none`` chooses how a sample of
  stored chunks is decoded: a chunk flagged with the configuration's
  compressor is decoded (lz4 block; zstd as ONE standalone frame that needs
  no dictionary), a chunk flagged raw is compared as it is, and one flagged
  with any other compressor differs; the stored bytes must be the file's
  (``stored_chunks_differ``), and where the configuration states a
  compressor, at least one sampled chunk must carry its flag
  (``stored_compressed_compared``: a pack that stored every chunk raw is no
  compressed pack);
* it digests every file of the dictionary image: a sampled chunk whose digest
  is in that set has to be referenced in a blob that is not the image's own,
  and every other one in the image's own, whose id is the sha256 of the blob
  section, taken here.

A second witness, not the decider: ``--backend hybrid`` (the program's C++
host lane, which shares ``converter/stream.py`` with the device lane)
converts the same tars once; every verb's result line and every byte of the
kept artifacts must equal its. Plus the counters that tell a device pack from
one that quietly ran on the host lanes: the lane's byte counter has to have
grown by exactly the tar bytes of every pack sent to it (a pack that ran on
the host adds none), whatever the number of batches a pack makes of its layer.
"""

from __future__ import annotations

import argparse
import filecmp
import hashlib
import os
import tarfile
import time

import numpy as np

from benchmark import program, reference

COMPRESSOR_MASK, FLAG_BATCH = 0xF, 0x200  # RAFS chunk flags
COMPRESSOR_NONE, COMPRESSOR_ZSTD, LZ4_BLOCK = 0x1, 0x2, 0x4  # the values of the mask; 0 is raw too
COMPRESSOR_FLAGS = {"none": COMPRESSOR_NONE, "zstd": COMPRESSOR_ZSTD, "lz4_block": LZ4_BLOCK}
DECODERS = {COMPRESSOR_ZSTD: reference.zstd_frame_decode, LZ4_BLOCK: reference.lz4_block_decode}
READ_GROUP_BYTES = 64 << 20  # sampled files read, cut and digested together


def arms(config: dict) -> tuple[dict, str]:
    """The pack arguments the reference follows, read from the configuration's
    ``pack_args`` as ``cmd/convert.py`` reads them (its defaults where a flag
    is absent, the last of a repeated one) -> (the cut and digest arms as
    ``reference.plain_chunks_many`` takes them, the compressor). A value the
    reference has no arm for is refused (``SystemExit``)."""
    ap = argparse.ArgumentParser(prog="pack_args", add_help=False, allow_abbrev=False)
    ap.add_argument("--chunking", default="cdc", choices=("cdc", "fixed"))
    ap.add_argument("--digester", default="sha256", choices=tuple(reference.DIGESTERS))
    ap.add_argument("--compressor", default="lz4_block", choices=tuple(COMPRESSOR_FLAGS))
    ap.add_argument("--chunk-size", type=lambda v: int(v, 0), default=0x100000)
    got, _others = ap.parse_known_args(config["pack_args"])
    return {"avg": got.chunk_size, "chunking": got.chunking, "digester": got.digester}, got.compressor


def check(name: str, value, limit, rule: str = "<=") -> dict:
    ok = value <= limit if rule == "<=" else value >= limit
    return {"name": name, "value": value, "limit": limit, "rule": rule, "ok": bool(ok)}


def run_reference(loop, ref_dir: str) -> dict:
    """One whole convert on the host lane -> {(verb, layer): result line}."""
    os.makedirs(ref_dir, exist_ok=True)
    return {(verb, li): program.cli(argv) for verb, li, _n, argv in loop.verbs(ref_dir, backend="hybrid")}


def compare(loop, records: list[dict], kept: list[str], ref_dir: str, ref_lines: dict,
            counters: dict, log, setup: tuple | list = ()) -> list[dict]:
    """records: the window's verbs, whoever sent them; kept: directories of
    window converts, any caller's; counters: the lane's, since before set-up;
    setup: set-up's verbs (sent to the device lane too)."""
    checks = [check("verbs_failed", sum(not r["ok"] for r in records), 0)]
    checks.append(check("result_lines_differ", sum(
        r["ok"] and r["result"] != ref_lines[(r["verb"], r["layer"])] for r in records), 0))
    differ = compared = 0
    for d in kept:
        for name in loop.files():
            if os.path.exists(os.path.join(d, name)):
                compared += 1
                differ += not filecmp.cmp(os.path.join(d, name), os.path.join(ref_dir, name), shallow=False)
    checks.append(check("artifacts_differ", differ, 0))
    checks.append(check("artifacts_compared", compared, len(loop.files()), ">="))
    own = [blob_sha256(os.path.join(kept[0], f"layer{li}.nydus")) for li in range(len(loop.tars))]
    checks.append(check("blob_ids_differ", sum(
        r["ok"] and r["verb"] == "pack" and r["result"].get("blob_id") != own[r["layer"]] for r in records), 0))
    checks += plain_checks(loop, kept[0], set(own), log)
    if loop.dict_boot:
        hits = [dict_hits(d, loop) for d in (kept[0], ref_dir)]
        checks.append(check("dict_hits_differ", abs(hits[0] - hits[1]), 0))
    fused = [r for r in [*setup, *records] if r["verb"] == "pack"]  # every pack sent to the device lane
    checks.append(check("dispatch_gap", counters["dispatches"] - len(fused), 0, ">="))  # a batch or more a pack
    checks.append(check("dispatched_bytes_gap", abs(counters["bytes"] - sum(r["bytes"] for r in fused)), 0))
    checks.append(check("host_fallbacks", counters["host_fallbacks"], 0))
    return checks


def blob_sha256(path: str) -> str:
    """A blob's id is the sha256 of its bytes: of the layer artifact's blob section."""
    with open(path, "rb") as f:
        return hashlib.sha256(program.layer_blob_data(f.read())).hexdigest()


def dict_hits(directory: str, loop) -> int:
    """Chunks of the image resolved into blobs of another image."""
    layers = [os.path.join(directory, f"layer{li}.nydus") for li in range(len(loop.tars))]
    own, n = {blob_sha256(path) for path in layers}, 0
    for path in layers:
        with open(path, "rb") as f:
            bs = program.layer_bootstrap(f.read())
        n += sum(bs.blobs[c.blob_index].blob_id not in own for c in bs.chunks)
    return n


def dictionary_digests(loop, log) -> set:
    """The digest of every chunk of every file of the dictionary image, by
    the plain reference: what a pack with that dictionary may not store again."""
    t0, held = time.perf_counter(), set()
    for chunks in reference.plain_chunks_many(loop.dict_files, **arms(loop.config)[0]):
        held.update(digest for _size, digest in chunks)
    if loop.dict_files:
        log("plain_dictionary", files=len(loop.dict_files), digests=len(held), wall_s=time.perf_counter() - t0)
    return held


def plain_checks(loop, directory: str, own_ids: set, log) -> list[dict]:
    """A sample of files, drawn from the seed, against the plain reference."""
    t0 = time.perf_counter()
    cut, compressor = arms(loop.config)
    want_flag = COMPRESSOR_FLAGS[compressor]
    rng = np.random.default_rng([int(loop.seed), 0xC0])
    budget = loop.cell["plain_sample_mib"] << 20
    n_files = n_chunks = files_differ = n_stored = n_compressed = stored_differ = dedup_differ = hits_expected = 0
    held = dictionary_digests(loop, log)
    for li, members in enumerate(loop.members):
        order = sorted(range(len(members)), key=lambda i: -members[i].size)[:1]  # the largest
        order += [int(i) for i in rng.permutation(len(members))]
        picked, used = [], 0
        for i in order:
            if used >= budget // len(loop.members):
                break
            if i not in picked:
                picked.append(i)
                used += members[i].size
        with open(os.path.join(directory, f"layer{li}.nydus"), "rb") as f:
            layer_blob = f.read()
        bs, blob = program.layer_bootstrap(layer_blob), program.layer_blob_data(layer_blob)
        by_path = {ino.path: ino for ino in bs.inodes}
        own = [i for i, b in enumerate(bs.blobs) if b.blob_id in own_ids]
        stored_left = loop.cell["stored_sample_chunks"] // len(loop.members)
        with tarfile.open(loop.tars[li]) as tf:
            for m, data, want in plain_files(tf, [members[i] for i in picked], cut):
                ino = by_path.get("/" + m.name)
                recs = bs.chunks[ino.chunk_index:ino.chunk_index + ino.chunk_count] if ino else []
                got = [(c.uncompressed_size, c.digest) for c in recs]
                n_files += 1
                n_chunks += len(want)
                files_differ += got != want
                if got == want:  # held by the dictionary: referenced there; new: in the image's own blob
                    dedup_differ += sum((digest in held) == (c.blob_index in own) for (_s, digest), c in zip(want, recs))
                    hits_expected += sum(digest in held for _s, digest in want)
                pos, of_file = 0, 0
                for c in recs:
                    if (stored_left > 0 and of_file < 2 and c.blob_index in own
                            and not c.flags & FLAG_BATCH):
                        stored_left -= 1
                        of_file += 1
                        n_stored += 1
                        flag = c.flags & COMPRESSOR_MASK
                        n_compressed += flag == want_flag
                        raw = blob[c.compressed_offset:c.compressed_offset + c.compressed_size]
                        try:
                            if flag == want_flag and flag in DECODERS:
                                raw = DECODERS[flag](raw, c.uncompressed_size)
                            elif flag not in (0, COMPRESSOR_NONE):
                                raise ValueError(f"stored with compressor flag {flag:#x}, not {compressor}'s")
                            stored_differ += raw != data[pos:pos + c.uncompressed_size].tobytes()
                        except (ValueError, IndexError):
                            stored_differ += 1
                    pos += c.uncompressed_size
    log("plain_reference", files=n_files, chunks=n_chunks, stored_chunks=n_stored, dictionary_hits=hits_expected,
        arms={**cut, "compressor": compressor}, wall_s=time.perf_counter() - t0)
    return [check("plain_files_differ", files_differ, 0),
            check("plain_chunks_compared", n_chunks, 1, ">="),
            check("stored_chunks_differ", stored_differ, 0),
            check("stored_chunks_compared", n_stored, 1, ">="),
            check("stored_compressed_compared", n_compressed, 0 if compressor == "none" else 1, ">="),
            check("dedup_differ", dedup_differ, 0),
            check("dictionary_hits_expected", hits_expected, 1 if loop.dict_files else 0, ">=")]


def plain_files(tf, members: list, cut: dict):
    """(member, its bytes, the plain reference's chunks) for each member of
    the tar, in order; files are read, cut and digested together, about
    ``READ_GROUP_BYTES`` at a time (a larger file alone)."""
    infos = {m.name: m for m in tf.getmembers()}
    start = 0
    while start < len(members):
        end, held = start + 1, members[start].size
        while end < len(members) and held + members[end].size <= READ_GROUP_BYTES:
            held += members[end].size
            end += 1
        datas = [np.frombuffer(tf.extractfile(infos[m.name]).read(), np.uint8) for m in members[start:end]]
        yield from zip(members[start:end], datas, reference.plain_chunks_many(datas, **cut))
        start = end
