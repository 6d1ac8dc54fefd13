"""The comparison that decides ``correct``, made after the window on what the
window itself wrote. Every number has its limit beside it; all are exact.

The decider is ``benchmark/reference.py`` (imports nothing of the program,
takes nothing it has made):

* it cuts and digests a seed-drawn sample of the tars' files, the largest
  among them: the chunk records of the kept artifacts must say the same;
* it decodes a sample of stored chunks: the stored bytes must be the file's;
* it digests every file of the dictionary image: a sampled chunk whose digest
  is in that set has to be referenced in a blob that is not the image's own,
  and every other one in the image's own, whose id is the sha256 of the blob
  section, taken here.

A second witness, not the decider: ``--backend hybrid`` (the program's C++
host lane, which shares ``converter/stream.py`` with the device lane)
converts the same tars once; every verb's result line and every byte of the
kept artifacts must equal its. Plus the counters that tell a device pack from
one that quietly ran on the host lanes.
"""

from __future__ import annotations

import filecmp
import hashlib
import os
import tarfile
import time

import numpy as np

from benchmark import program, reference

COMPRESSOR_MASK, LZ4_BLOCK, FLAG_BATCH = 0xF, 0x4, 0x200  # RAFS chunk flags


def check(name: str, value, limit, rule: str = "<=") -> dict:
    ok = value <= limit if rule == "<=" else value >= limit
    return {"name": name, "value": value, "limit": limit, "rule": rule, "ok": bool(ok)}


def run_reference(loop, ref_dir: str) -> dict:
    """One whole convert on the host lane -> {(verb, layer): result line}."""
    os.makedirs(ref_dir, exist_ok=True)
    return {(verb, li): program.cli(argv) for verb, li, _n, argv in loop.verbs(ref_dir, backend="hybrid")}


def compare(loop, records: list[dict], kept: list[str], ref_dir: str, ref_lines: dict,
            counters: dict, log) -> list[dict]:
    """records: the window's verbs; kept: directories of window converts."""
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
    checks.append(check("dispatch_gap", abs(counters["dispatches"] - loop.fused_packs), 0))
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
    t0, avg, held = time.perf_counter(), loop.config["chunk_size"], set()
    for data in loop.dict_files:
        held.update(digest for _size, digest in reference.plain_chunks(data, avg))
    if loop.dict_files:
        log("plain_dictionary", files=len(loop.dict_files), digests=len(held), wall_s=time.perf_counter() - t0)
    return held


def plain_checks(loop, directory: str, own_ids: set, log) -> list[dict]:
    """A sample of files, drawn from the seed, against the plain reference."""
    t0 = time.perf_counter()
    avg = loop.config["chunk_size"]
    rng = np.random.default_rng([int(loop.seed), 0xC0])
    budget = loop.cell["plain_sample_mib"] << 20
    n_files = n_chunks = files_differ = n_stored = stored_differ = dedup_differ = hits_expected = 0
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
            infos = {m.name: m for m in tf.getmembers()}
            for i in picked:
                data = np.frombuffer(tf.extractfile(infos[members[i].name]).read(), np.uint8)
                want = reference.plain_chunks(data, avg)
                ino = by_path.get("/" + members[i].name)
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
                        raw = blob[c.compressed_offset:c.compressed_offset + c.compressed_size]
                        try:
                            if c.flags & COMPRESSOR_MASK == LZ4_BLOCK:
                                raw = reference.lz4_block_decode(raw, c.uncompressed_size)
                            stored_differ += raw != data[pos:pos + c.uncompressed_size].tobytes()
                        except (ValueError, IndexError):
                            stored_differ += 1
                    pos += c.uncompressed_size
    log("plain_reference", files=n_files, chunks=n_chunks, stored_chunks=n_stored, dictionary_hits=hits_expected,
        wall_s=time.perf_counter() - t0)
    return [check("plain_files_differ", files_differ, 0),
            check("plain_chunks_compared", n_chunks, 1, ">="),
            check("stored_chunks_differ", stored_differ, 0),
            check("stored_chunks_compared", n_stored, 1, ">="),
            check("dedup_differ", dedup_differ, 0),
            check("dictionary_hits_expected", hits_expected, 1 if loop.dict_files else 0, ">=")]
