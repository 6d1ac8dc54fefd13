"""Image generator: the shape from the configuration, the small files and the
order from --seed, and ONE data set per configuration for the files that
content-defined chunking cuts.

A copy of ``bench.build_node_shaped_layers`` (log-normal file sizes,
40/40/20 text/binary/random) with its one RNG split in three. File names,
sizes, kinds, the layer split and which files of a second image come from a
pool are drawn from ``shape_seed``; the bytes of the files CDC cuts (larger
than a quarter of the chunk size: ~90% of the bytes at 64 KiB chunks, about
half at 1 MiB) from ``data_seed``; the bytes of every other file and the
files' order in the tar from ``--seed``. So all seeds of a cell share one set
of cuts, digests and one bucket plan, in another order with other small
files: the seeds do NOT vary the data the lane cuts. Why: see ``layer_bytes``.
The program receives only the tars.
"""

from __future__ import annotations

import io
import tarfile
from dataclasses import dataclass

import numpy as np

TEXT_BASE_BYTES = 1 << 20
BINARY_ZERO_BELOW = 141  # of 256: ~55% of a "binary" file's bytes are zero


@dataclass(frozen=True)
class Member:
    name: str
    size: int
    kind: str  # text | binary | random | pooled
    pool_index: int = -1


def image_shape(shape_seed: int, law: dict, total_bytes: int, weights, pool_sizes=None,
                reuse_fraction: float = 0.0) -> list[list[Member]]:
    """The image's schema: per layer, its members in tar order."""
    rng = np.random.default_rng([int(shape_seed), 0x5A])
    w = np.asarray(weights, dtype=np.float64)
    budgets = (w / w.sum() * total_bytes).astype(np.int64)
    p_text, p_binary = law["mix"]["text"], law["mix"]["binary"]
    layers = []
    for li, budget in enumerate(budgets):
        members, used, fi = [], 0, 0
        while used < budget:
            if pool_sizes is not None and rng.random() < reuse_fraction:
                idx = int(rng.integers(0, len(pool_sizes)))
                size, kind = int(pool_sizes[idx]), "pooled"
            else:
                size = int(np.clip(rng.lognormal(law["lognormal_mu"], law["lognormal_sigma"]),
                                   law["min_bytes"], law["max_bytes"]))
                size = min(size, int(budget - used)) or law["min_bytes"]
                r, idx = rng.random(), -1
                kind = "text" if r < p_text else ("binary" if r < p_text + p_binary else "random")
            members.append(Member(f"layer{li}/d{fi % 97}/f{fi}.bin", size, kind, idx))
            used += size
            fi += 1
        layers.append(members)
    return layers


def _random_bytes(rng, n: int) -> np.ndarray:
    words = rng.integers(0, 2**64, -(-n // 8), dtype=np.uint64, endpoint=False)
    return words.view(np.uint8)[:n]


def _text_base(rng) -> np.ndarray:
    """1 MiB of word-like ASCII (compresses ~3-4x under lz4)."""
    words = [rng.integers(97, 123, int(rng.integers(3, 11)), dtype=np.uint8) for _ in range(400)]
    picks = rng.integers(0, len(words), TEXT_BASE_BYTES // 4 + 1)
    space = np.frombuffer(b" ", dtype=np.uint8)
    parts = [p for i in picks for p in (words[i], space)]
    return np.concatenate(parts)[:TEXT_BASE_BYTES]


def layer_bytes(seed: int, data_seed: int, fixed_above: int, salt: int, layer: int,
                members: list[Member], pool=None) -> list[np.ndarray]:
    """Every member's bytes (``salt`` keeps two images of one run apart).
    Files larger than ``fixed_above`` — the ones content-defined chunking cuts
    — take theirs from ``data_seed``, the others from ``seed``: a file's cuts
    follow its bytes and the program compiles one ``_pass2`` per bucket plan
    (rows per size class), so bytes drawn from the seed give every seed its
    own programs (70-257 s each: a 528 s run) and, at 1 MiB chunks, its own
    speed (a one-row class at 65,536 blocks: 56 MiB/s against 96). Which plan
    a configuration runs is its ``data_seed``'s, stated in its file. Pooled
    members are the pool's arrays themselves."""
    out = [pool[m.pool_index] if m.kind == "pooled" else None for m in members]
    for key, fixed in (([int(data_seed), 0xF1], True), ([int(seed)], False)):
        idx = [i for i, m in enumerate(members) if m.kind != "pooled" and (m.size > fixed_above) == fixed]
        for i, data in zip(idx, _drawn_bytes(key, salt, layer, [members[i] for i in idx])):
            out[i] = data
    return out


def _drawn_bytes(key: list[int], salt: int, layer: int, members: list[Member]) -> list[np.ndarray]:
    text = _text_base(np.random.default_rng(key + [0x7E]))
    rng = np.random.default_rng(key + [int(salt), int(layer)])
    n_rand = sum(m.size for m in members if m.kind in ("binary", "random"))
    n_bin = sum(m.size for m in members if m.kind == "binary")
    noise, keep = _random_bytes(rng, n_rand), _random_bytes(rng, n_bin) >= BINARY_ZERO_BELOW
    offsets = rng.integers(0, TEXT_BASE_BYTES, len(members))
    out, pos, bpos = [], 0, 0
    for m, off in zip(members, offsets):
        if m.kind == "text":
            reps = -(-(m.size + int(off)) // TEXT_BASE_BYTES)
            out.append(np.tile(text, reps)[int(off):int(off) + m.size] if reps > 1
                       else text[int(off):int(off) + m.size])
        else:
            data = noise[pos:pos + m.size]
            pos += m.size
            if m.kind == "binary":  # ELF-ish: random bytes with zero runs
                data *= keep[bpos:bpos + m.size]
                bpos += m.size
            out.append(data)
    return out


def shuffled(seed: int, salt: int, layer: int, members: list[Member], datas: list) -> tuple[list, list]:
    """The layer's files in the tar order of this seed: the same set of
    sizes for every seed, in another order."""
    order = np.random.default_rng([int(seed), 0x0D, int(salt), int(layer)]).permutation(len(members))
    return [members[i] for i in order], [datas[i] for i in order]


def write_tar(path: str, members: list[Member], datas: list[np.ndarray]) -> int:
    """GNU tar of the members, as bench's generator writes it -> bytes."""
    with open(path, "wb") as f:
        with tarfile.open(fileobj=f, mode="w", format=tarfile.GNU_FORMAT) as tf:
            for m, data in zip(members, datas):
                ti = tarfile.TarInfo(m.name)
                ti.size = m.size
                tf.addfile(ti, io.BytesIO(memoryview(data)))
        return f.tell()
