"""Image shape and the cut files' bytes from the configuration, the rest from the seed."""

import os
import sys
import tarfile

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import reference, run  # noqa: E402
from benchmark.traffic import convert_loop  # noqa: E402


def _tars(tmp_path, seed, mix="fresh", **config_kw):
    config = run.load(run.HERE, "configs", "node21-64k.json") | config_kw
    config["image_mib"] = 4
    cell = run.load(run.HERE, "traffic", "mixes", f"{mix}.json")
    work = tmp_path / str(seed)
    work.mkdir()
    loop = convert_loop.build(cell, config, seed, str(work), lambda *_a, **_k: None)
    loop.generate()
    out = []
    for path in loop.tars:
        with tarfile.open(path) as tf:
            out.append([(m.name, m.size, tf.extractfile(m).read()) for m in tf if m.isreg()])
    return out


def test_same_shape_other_data(tmp_path):
    """Two seeds: the same files (names, sizes) in another order; the files
    CDC leaves whole (<= avg/4) have other bytes, the ones it cuts the same."""
    a, b = _tars(tmp_path, 3_000_000_001), _tars(tmp_path, 5)
    assert len(a) == 2 and sum(len(layer) for layer in a) > 50
    for la, lb in zip(a, b):
        assert [n for n, _s, _d in la] != [n for n, _s, _d in lb]
        da, db = {n: d for n, _s, d in la}, {n: d for n, _s, d in lb}
        assert {n: len(d) for n, d in da.items()} == {n: len(d) for n, d in db.items()}
        whole = [n for n, d in da.items() if len(d) <= 0x10000 // 4]
        cut = [n for n, d in da.items() if len(d) > 0x10000 // 4]
        assert whole and cut
        assert all(da[n] != db[n] for n in whole) and all(da[n] == db[n] for n in cut)


def test_data_seed_is_the_cut_files_bytes(tmp_path):
    """Another data_seed: the same files in the same order, other bytes in the files CDC cuts and only there."""
    (tmp_path / "x").mkdir()
    (tmp_path / "y").mkdir()
    a, b = _tars(tmp_path / "x", 9), _tars(tmp_path / "y", 9, data_seed=1)
    for la, lb in zip(a, b):
        assert [(n, s) for n, s, _d in la] == [(n, s) for n, s, _d in lb]
        assert all((da != db) == (s > 0x10000 // 4) for (_n, s, da), (_m, _s, db) in zip(la, lb))


def test_same_seed_same_bytes(tmp_path):
    (tmp_path / "x").mkdir()
    (tmp_path / "y").mkdir()
    assert _tars(tmp_path / "x", 9) == _tars(tmp_path / "y", 9)


def test_plain_reference_cuts_follow_content():
    """A byte inserted at the front moves every later cut by one: the cuts are
    content-defined; and a file no longer than avg/4 is one chunk."""
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, 1 << 20, dtype=np.uint8)
    cuts = reference.plain_cuts(data, 0x10000)
    shifted = reference.plain_cuts(np.concatenate([[np.uint8(7)], data]), 0x10000)
    assert cuts[-1] == len(data) and all(0x4000 <= b - a <= 0x40000 for a, b in zip([0] + cuts[:-2], cuts[:-1]))
    assert [c + 1 for c in cuts[1:]] == shifted[1:]
    assert reference.plain_cuts(data[:0x4000], 0x10000) == [0x4000]


def test_lz4_block_decode():
    # literals "abcd", then a match of 8 at offset 4 (overlapping), then literals "xy"
    block = bytes([0x44]) + b"abcd" + bytes([4, 0]) + bytes([0x20]) + b"xy"
    assert reference.lz4_block_decode(block, 14) == b"abcdabcdabcdxy"
