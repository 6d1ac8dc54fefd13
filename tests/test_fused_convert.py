"""Fused device full-path differentials: the two-dispatch composition
(ops/fused_convert) must produce bit-identical cuts and digests to the
host oracle engine, and its dict-probe must match the host dict.

Runs the XLA formulation on the CPU backend (the gear Pallas kernel is
hardware-only: tests/test_chip_compile.py compiles it for the chip and
chip_smoke.py runs it there)."""

import hashlib
import io
import tarfile

import numpy as np
import pytest

from nydus_snapshotter_tpu import trace
from nydus_snapshotter_tpu.ops import cdc, fused_convert, mesh_pack
from nydus_snapshotter_tpu.ops.chunker import ChunkDigestEngine
from nydus_snapshotter_tpu.parallel.sharded_dict import (
    _build_host_tables,
    _table_max_depth,
)

CHUNK = 0x10000  # 64 KiB average so small corpora produce many chunks
SMALL = 0x1000  # 4 KiB average: the top class is 16 KiB, cheap on the CPU
R = fused_convert.ROW_FLOOR


def _corpus(seed: int, sizes: list[int]) -> list[bytes]:
    rng = np.random.default_rng(seed)
    out = []
    for i, size in enumerate(sizes):
        if i % 3 == 0:
            data = rng.integers(0, 256, size, dtype=np.uint8)
        elif i % 3 == 1:
            base = rng.integers(0, 256, max(1, size // 7), dtype=np.uint8)
            data = np.tile(base, 8)[:size]
        else:
            words = rng.integers(32, 127, size, dtype=np.uint8)
            data = words
        out.append(data.tobytes())
    return out


@pytest.fixture(scope="module")
def oracle():
    return ChunkDigestEngine(chunk_size=CHUNK, backend="numpy", digest_backend="numpy")


class TestFusedDifferential:
    def test_cuts_and_digests_match_oracle(self, oracle):
        streams = _corpus(7, [3, 100_000, 0, 700_001, 64, 250_000, 1_048_576])
        eng = fused_convert.FusedDeviceEngine(chunk_size=CHUNK)
        res = eng.process_many(streams)
        want = oracle.process_many(streams)
        assert len(res.cuts) == len(streams)
        for i, (got_cuts, got_digs, metas) in enumerate(
            zip(res.cuts, res.digests, want)
        ):
            want_cuts = np.asarray(
                [m.offset + m.size for m in metas], dtype=np.int64
            )
            np.testing.assert_array_equal(got_cuts, want_cuts, err_msg=f"stream {i}")
            assert got_digs == [m.digest for m in metas], f"stream {i}"

    def test_digests_are_real_sha256(self):
        streams = _corpus(11, [150_000, 80_000])
        eng = fused_convert.FusedDeviceEngine(chunk_size=CHUNK)
        res = eng.process_many(streams)
        for s, cuts, digs in zip(streams, res.cuts, res.digests):
            prev = 0
            for cut, d in zip(cuts, digs):
                assert hashlib.sha256(s[prev:cut]).digest() == d
                prev = int(cut)

    def test_probe_matches_host_dict(self):
        streams = _corpus(13, [400_000, 200_000])
        eng = fused_convert.FusedDeviceEngine(chunk_size=CHUNK)
        first = eng.process_many(streams)
        flat = [d for digs in first.digests for d in digs]
        digests_u32 = np.frombuffer(b"".join(flat), dtype=">u4").astype(
            np.uint32
        ).reshape(-1, 8)
        keys, values = _build_host_tables(digests_u32, 1)
        depth = _table_max_depth(keys, values)
        # second corpus: one stream re-used verbatim (all hits), one fresh
        streams2 = [streams[0], _corpus(17, [300_000])[0]]
        res = eng.process_many(
            streams2, chunk_dict=(keys[0], values[0]), depth=depth
        )
        assert res.probe is not None
        n0 = len(res.digests[0])
        hits = res.probe[:n0]
        # stream 0 is byte-identical to dict source: every chunk must hit,
        # and each hit value is the 1-based insertion index
        assert (hits > 0).all()
        for d, h in zip(res.digests[0], hits):
            assert flat[int(h) - 1] == d
        # fresh random stream: digests absent from the dict must miss
        fresh_hits = res.probe[n0:]
        fresh_set = {d for d in res.digests[1]}
        expected_miss = [d not in set(flat) for d in res.digests[1]]
        for miss, h in zip(expected_miss, fresh_hits):
            if miss:
                assert h == 0
        assert len(fresh_set) > 0

    def test_empty_and_tiny_batch(self):
        eng = fused_convert.FusedDeviceEngine(chunk_size=CHUNK)
        res = eng.process_many([b"", b"x"])
        assert list(res.cuts[0]) == []
        assert list(res.cuts[1]) == [1]
        assert res.digests[1] == [hashlib.sha256(b"x").digest()]

    def test_overflow_raises(self, monkeypatch):
        # Pathological inputs can exceed the static candidate capacity;
        # the engine must refuse loudly (callers fall back to the windowed
        # path) rather than silently truncate candidates — truncation
        # would yield WRONG cuts. Force the condition by shrinking the cap.
        monkeypatch.setattr(
            fused_convert, "_wcap_for", lambda n, bits, floor=1024: 2
        )
        eng = fused_convert.FusedDeviceEngine(chunk_size=CHUNK)
        data = _corpus(23, [1 << 20])[0]
        with pytest.raises(fused_convert.FusedOverflow):
            eng.process_many([data])


def _thin_top_batch(top_rows: int) -> list[bytes]:
    """Streams, at 4 KiB chunks, whose plan's top class holds exactly
    ``top_rows`` rows: a run of that many max-size blocks, each of one
    byte value (the gear hash of a constant run meets neither mask, so
    every cut inside it is the forced one), among files that CDC cuts and
    small ones it leaves whole."""
    max_size = cdc.CDCParams(SMALL).max_size
    run = b"".join(
        np.full(max_size, 1 + j, np.uint8).tobytes() for j in range(top_rows)
    )
    cut_a, cut_b, small = _corpus(71, [30_000, 20_000, 700])
    return [cut_a, b"", run, small, cut_b, b"x"]


class TestRowFloor:
    """No digest class is dispatched with fewer than ROW_FLOOR rows
    (fused_convert.bucket_rows): the padding rows change no cut, no
    digest and no probe hit."""

    @pytest.mark.parametrize("digester", ["sha256", "blake3"])
    @pytest.mark.parametrize("top_rows", sorted({1, 2, 3, R + 1, 5}))
    def test_thin_top_class_matches_oracle(self, digester, top_rows):
        from nydus_snapshotter_tpu.utils import blake3 as pyb3

        streams = _thin_top_batch(top_rows)
        eng = fused_convert.FusedDeviceEngine(chunk_size=SMALL, digester=digester)
        want = ChunkDigestEngine(
            chunk_size=SMALL, backend="numpy", digest_backend="numpy"
        ).process_many(streams)
        if digester == "blake3":
            ref, words = pyb3.blake3, "<u4"
        else:
            ref, words = (lambda b: hashlib.sha256(b).digest()), ">u4"
        flat = [
            ref(s[m.offset : m.offset + m.size])
            for s, metas in zip(streams, want)
            for m in metas
        ]
        # a dictionary of the batch's own chunks: every probe row has to
        # name its own chunk, so a row base shifted by padding rows shows
        keys, values = _build_host_tables(
            np.frombuffer(b"".join(flat), dtype=words).astype(np.uint32).reshape(-1, 8), 1
        )
        res = eng.process_many(
            streams, chunk_dict=(keys[0], values[0]), depth=_table_max_depth(keys, values)
        )
        for i, (cuts, metas) in enumerate(zip(res.cuts, want)):
            np.testing.assert_array_equal(
                cuts, [m.offset + m.size for m in metas], err_msg=f"stream {i}"
            )
        assert [d for digs in res.digests for d in digs] == flat
        assert len(res.probe) == len(flat)
        for d, hit in zip(flat, res.probe):
            assert hit > 0 and flat[int(hit) - 1] == d

        table, pos = [], 0
        for s in streams:
            table.append((pos, len(s)))
            pos += len(s)
        top = eng.plan_buckets(table, res.cuts)[0][-1]
        assert top.cap_blocks == eng._blocks_of(eng.params.max_size)
        assert top.count == top_rows
        assert len(top.offsets) == max(R, fused_convert._pow2_ceil(top_rows))

    @pytest.mark.parametrize("n_devices", [1, 2, 4])
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_no_class_under_the_floor_and_order_skips_padding(self, seed, n_devices):
        """Host only: plan_buckets and plan_mesh_pack over drawn chunk
        sizes, log-uniform so that the long classes are thin."""
        rng = np.random.default_rng(300 + seed)
        eng = fused_convert.FusedDeviceEngine(chunk_size=SMALL)
        table, cuts, total = [], [], 0
        for _ in range(int(rng.integers(1, 30))):
            sizes = np.exp(
                rng.uniform(0, np.log(eng.params.max_size), int(rng.integers(1, 6)))
            ).astype(np.int64)
            table.append((total, int(sizes.sum())))
            cuts.append(np.cumsum(sizes))
            total += int(sizes.sum())
        # and one max-size chunk: the top class holds that row alone
        table.append((total, eng.params.max_size))
        cuts.append(np.asarray([eng.params.max_size], dtype=np.int64))
        total += eng.params.max_size
        buckets, order = eng.plan_buckets(table, cuts)
        by_cap = {b.cap_blocks: b for b in buckets}
        assert buckets[-1].count == 1 < R
        for b in buckets:
            assert len(b.offsets) == len(b.sizes) == fused_convert.bucket_rows(b.count) >= R
            assert not b.sizes[b.count :].any() and not b.offsets[b.count :].any()
        assert all(row < by_cap[cap].count for cap, row in order)
        assert len(order) == sum(len(c) for c in cuts)

        plan = mesh_pack.plan_mesh_pack(
            buckets, order, total, n_devices, halo_bytes=eng.max_read_span()
        )
        sharded = {sb.cap_blocks: sb for sb in plan.buckets}
        for sb in plan.buckets:
            assert sb.rows_per_device == fused_convert.bucket_rows(max(sb.counts)) >= R
            assert len(sb.sizes) == n_devices * sb.rows_per_device
        for (cap, row), (_, old_row) in zip(plan.order, order):
            d, i = divmod(row, sharded[cap].rows_per_device)
            assert i < sharded[cap].counts[d]
            assert sharded[cap].sizes[row] == by_cap[cap].sizes[old_row]


def _edge_tar() -> tuple[bytes, list[tuple[int, int]]]:
    """-> (a layer tar cut off after its last data block, the (data
    offset, size) of every regular member in tar order), at 4 KiB chunks:
    an empty file, one of min_size bytes (one chunk, no candidate
    judged), one chunk of exactly max_size, files that CDC cuts, two of whole
    512-byte blocks with nothing but the second's header between them,
    and a last one whose data ends with the buffer."""
    p = cdc.CDCParams(SMALL)
    cut_a, cut_b, seam_a, seam_b, last = _corpus(91, [30_001, 70_003, 40 * 512, 59 * 512, 24 * 512])
    run = bytes([9]) * p.max_size  # one forced cut, at max_size (_thin_top_batch)
    files = [b"", cut_a, b"m" * p.min_size, run, b"x", cut_b, seam_a, seam_b, last]
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w", format=tarfile.GNU_FORMAT) as tf:
        for i, data in enumerate(files):
            info = tarfile.TarInfo(f"layer/f{i}")
            info.size = len(data)
            tf.addfile(info, io.BytesIO(data))
    tar = buf.getvalue()
    with tarfile.open(fileobj=io.BytesIO(tar)) as tf:
        extents = [(m.offset_data, m.size) for m in tf]
    assert [tar[off : off + size] for off, size in extents] == files
    assert extents[7][0] == extents[6][0] + extents[6][1] + 512
    end = extents[-1][0] + extents[-1][1]
    assert end % 512 == 0  # no padding after the last file's data
    return tar[:end], extents


def _every_other_chunk(eng, streams):
    """-> (a chunk dictionary of every other chunk of the batch, its probe
    depth): hits and misses for process_many(streams, chunk_dict=...)."""
    flat = [d for digs in eng.process_many(streams).digests for d in digs][::2]
    words = "<u4" if eng.digester == "blake3" else ">u4"
    keys, values = _build_host_tables(
        np.frombuffer(b"".join(flat), dtype=words).astype(np.uint32).reshape(-1, 8), 1
    )
    return (keys[0], values[0]), _table_max_depth(keys, values)


class TestExtentsEntry:
    """process_many(Extents(tar, extents)) is process_many(the members' slices):
    the tar is the lane's buffer and the plan's extents are its table,
    whether lane_buffer finds room behind the tar (no copy) or not (one
    bulk copy), and whatever the room holds."""

    def test_the_seam_between_two_files_would_show(self):
        """The data is worth its name: were two files with a header
        between them resolved as one extent, the second's cuts would move."""
        tar, extents = _edge_tar()
        eng = fused_convert.FusedDeviceEngine(chunk_size=SMALL)
        (a_off, a_len), (b_off, b_len) = extents[6], extents[7]
        apart = eng.process_many(fused_convert.Extents(tar, [extents[6], extents[7]])).cuts
        merged = eng.process_many(
            fused_convert.Extents(tar, [(a_off, b_off + b_len - a_off)])
        ).cuts[0]
        leaked = merged[merged > b_off - a_off] - (b_off - a_off)
        assert list(apart[1]) != list(leaked)

    @pytest.mark.parametrize("room", ["slack", "slack-dirty", "bytes"])
    @pytest.mark.parametrize("with_dict", [False, True])
    @pytest.mark.parametrize("digester", ["sha256", "blake3"])
    def test_extents_match_process_many(self, digester, with_dict, room):
        tar, extents = _edge_tar()
        eng = fused_convert.FusedDeviceEngine(chunk_size=SMALL, digester=digester)
        streams = [tar[off : off + size] for off, size in extents]
        chunk_dict, depth = _every_other_chunk(eng, streams) if with_dict else (None, 8)
        want = eng.process_many(streams, chunk_dict=chunk_dict, depth=depth)

        npad = fused_convert.padded_length(len(tar), eng.params.max_size)
        if room == "bytes":
            data, copied_want = tar, len(tar)
        else:
            big = fused_convert.zeroed_buffer(npad)
            if room == "slack-dirty":  # what follows the tar is never judged
                big[:] = np.random.default_rng(93).integers(0, 256, npad, dtype=np.uint8)
            big[: len(tar)] = np.frombuffer(tar, dtype=np.uint8)
            data, copied_want = big[: len(tar)], 0
        trace.configure(enabled=True)
        try:
            got = eng.process_many(
                fused_convert.Extents(data, extents), chunk_dict=chunk_dict, depth=depth
            )
            (layout,) = [s.attrs for s in trace.snapshot_spans() if s.name == "pack:lane.layout"]
        finally:
            trace.reset()
        assert layout["copied_bytes"] == copied_want

        assert len(got.cuts) == len(extents)
        for i, (g, w) in enumerate(zip(got.cuts, want.cuts)):
            np.testing.assert_array_equal(g, w, err_msg=f"file {i}")
        assert got.digests == want.digests
        assert [len(c) for c in got.cuts][:5] == [0, len(want.cuts[1]), 1, 1, 1]
        assert sum(len(c) for c in got.cuts) > len(extents) + 20  # CDC really cut
        if with_dict:
            np.testing.assert_array_equal(got.probe, want.probe)
            assert (got.probe > 0).any() and (got.probe == 0).any()
        else:
            assert got.probe is None and want.probe is None

    @pytest.mark.parametrize("extent", [(-1, 4), (0, -4), (9_000, 2_000)])
    def test_an_extent_outside_the_buffer_is_refused(self, extent):
        eng = fused_convert.FusedDeviceEngine(chunk_size=SMALL)
        with pytest.raises(ValueError, match="outside its 10000-byte buffer"):
            eng.process_many(fused_convert.Extents(bytes(10_000), [(0, 100), extent]))

    def test_lane_buffer_takes_the_room_behind_the_data_or_copies_once(self):
        W = fused_convert.WINDOW
        big = fused_convert.zeroed_buffer(3 * W)
        assert big.ctypes.data % 4096 == 0 and big.size == 3 * W and not big.any()
        big[:1008] = 7
        for data in (big[:1000], big[8:1008]):  # room from its first byte on
            buf, copied = fused_convert.lane_buffer(data, 2 * W)
            assert copied == 0 and buf.size == 2 * W and buf.ctypes.data == data.ctypes.data
        for data in (
            big[W + 5000 : W + 6000],  # too little room behind it
            big[:2000:2],  # not the bytes as they lie
            big[:1000].copy(),  # owns its bytes, nothing behind them
            np.frombuffer(bytes(1000), dtype=np.uint8),
        ):
            buf, copied = fused_convert.lane_buffer(data, 2 * W)
            assert copied == data.size and buf.size == 2 * W and not np.shares_memory(buf, data)
            assert bytes(buf[: data.size]) == bytes(data) and not buf[data.size :].any()
            assert buf.ctypes.data % 4096 == 0

    @pytest.mark.parametrize("total", [0, 1, 100_000, (1 << 22) - 16_448, (1 << 22) - 16_447, 600 << 20])
    def test_padded_length_is_layouts_rule(self, total):
        max_size = cdc.CDCParams(SMALL).max_size
        npad = fused_convert.padded_length(total, max_size)
        assert npad % fused_convert.WINDOW == 0 and npad >= total + max_size + 64
        assert npad < 2 * (total + max_size + 64) + fused_convert.WINDOW
        if total <= 1 << 22:
            eng = fused_convert.FusedDeviceEngine(chunk_size=SMALL)
            assert eng.layout([np.zeros(total, np.uint8)])[0].size == npad

    @pytest.mark.parametrize("chunk_size", [SMALL, 0x100000])
    def test_padded_length_refuses_what_int32_cannot_address(self, chunk_size):
        max_size = cdc.CDCParams(chunk_size).max_size
        step = 1 << 28  # the 1/8-power-of-two step under 2 GiB
        last_ok = (1 << 31) - step - max_size - 64
        assert fused_convert.padded_length(last_ok, max_size) == (1 << 31) - step
        with pytest.raises(fused_convert.FusedOverflow):
            fused_convert.padded_length(last_ok + 1, max_size)
        # a layer past it goes as batches of whole files (tests/test_lane_batches.py);
        # what still declines is ONE file whose own bytes pad past it, and says its size
        with pytest.raises(fused_convert.FusedOverflow, match=f"one file of {last_ok + 1} bytes"):
            fused_convert.FusedDeviceEngine(chunk_size=chunk_size).process_batches(
                fused_convert.Extents(np.zeros(last_ok + 1, dtype=np.uint8), [(0, last_ok + 1)])
            )


# -- pass 2's word gather alone -------------------------------------------------

GATHER_CAP = 4  # SHA blocks a row: 256 bytes, 64 words
GATHER_SHA_SIZES = [0, 1, 3, 4, 55, 56, 63, 64, 65, 119, 120, GATHER_CAP * 64 - 9]
GATHER_B3_SIZES = [0, 1, 3, 4, 63, 64, 65, 1023, 1024, 1025, 2048]  # two leaves a row


def _chunk_and_padding_row(off: int, size: int):
    """-> (offs, sizes) of a batch of two: the chunk, and a padding row."""
    import jax.numpy as jnp

    return jnp.asarray(np.array([off, 0], np.int32)), jnp.asarray(np.array([size, 0], np.int32))


class TestWordGather:
    """_gather_pack_sha / _gather_pack_b3 against the host packings, byte
    for byte: the chunk's bytes reach the digest as words funnel-shifted
    out of the buffer's words, for every residue of the chunk's offset, and
    its padding is made by masks on words. Row 1 of every batch is a
    padding row (size 0, offset 0), as class_rows pads a class."""

    @pytest.fixture(scope="class")
    def lane(self):
        import jax
        import jax.numpy as jnp

        buf = np.random.default_rng(34).integers(0, 256, 1 << 14, dtype=np.uint8)
        words = fused_convert.lane_words(buf)
        assert np.shares_memory(words, buf) and words.dtype == np.uint32 and words.size == buf.size // 4
        sha = jax.jit(fused_convert._gather_pack_sha, static_argnums=3)
        b3 = jax.jit(fused_convert._gather_pack_b3, static_argnums=3)
        digest = jax.jit(fused_convert._gather_digest_sha, static_argnums=(3, 4))
        return buf, jnp.asarray(words), sha, b3, digest, _chunk_and_padding_row

    @staticmethod
    def _sha_blocks(chunk, cap):
        from nydus_snapshotter_tpu.ops import sha256

        want = np.zeros((cap, 16), np.uint32)  # beyond its padded blocks a row is zeros
        padded = sha256.pad_message_np(chunk)
        want[: len(padded)] = padded
        return want

    @pytest.mark.parametrize("size", GATHER_SHA_SIZES)
    @pytest.mark.parametrize("residue", [0, 1, 2, 3])
    def test_sha_blocks_are_pad_message_nps(self, lane, residue, size):
        from nydus_snapshotter_tpu.ops import sha256

        buf, words, sha, _b3, digest, rows = lane
        off = 1000 + residue
        blocks = np.asarray(sha(words, *rows(off, size), GATHER_CAP))
        assert blocks.shape == (GATHER_CAP, 16, 2)  # the rows on the last axis
        np.testing.assert_array_equal(blocks[:, :, 0], self._sha_blocks(buf[off : off + size], GATHER_CAP))
        np.testing.assert_array_equal(blocks[:, :, 1], self._sha_blocks(b"", GATHER_CAP))
        states = np.asarray(digest(words, *rows(off, size), GATHER_CAP, False))
        assert sha256.digest_to_bytes(states[0]) == hashlib.sha256(bytes(buf[off : off + size])).digest()
        assert sha256.digest_to_bytes(states[1]) == hashlib.sha256(b"").digest()

    @pytest.mark.parametrize("size", GATHER_B3_SIZES)
    @pytest.mark.parametrize("residue", [0, 1, 2, 3])
    def test_blake3_blocks_are_pack_messages_nps(self, lane, residue, size):
        from nydus_snapshotter_tpu.ops import blake3_jax

        buf, words, _sha, b3, _digest, rows = lane
        off = 2000 + residue
        blocks = np.asarray(b3(words, *rows(off, size), 2))
        want, _lengths = blake3_jax.pack_messages_np([buf[off : off + size], b""], leaf_capacity=2)
        np.testing.assert_array_equal(blocks, want)

    @pytest.mark.parametrize("digester", ["sha256", "blake3"])
    @pytest.mark.parametrize("residue", [0, 1, 2, 3])
    def test_a_max_size_chunk_that_ends_at_the_last_valid_byte(self, digester, residue):
        """The gather reads whole words, one past the chunk's capacity: the
        guard of padded_length (max_size + 64, here without its rounding up
        to a window) is room for it, so no dynamic_slice clamps its start."""
        import jax.numpy as jnp
        from nydus_snapshotter_tpu.ops import blake3_jax, sha256

        eng = fused_convert.FusedDeviceEngine(chunk_size=SMALL, digester=digester)
        size = eng.params.max_size
        total = 3 * size + residue
        buf = np.zeros(total + size + 64, np.uint8)
        buf[:total] = np.random.default_rng(residue).integers(0, 256, total, dtype=np.uint8)
        off, cap = total - size, eng._blocks_of(size)
        assert off & 3 == residue and off + eng.max_read_span() <= buf.size
        words = jnp.asarray(fused_convert.lane_words(buf))
        offs, sizes = _chunk_and_padding_row(off, size)
        if digester == "blake3":
            got = np.asarray(fused_convert._gather_pack_b3(words, offs, sizes, cap))[0]
            want = blake3_jax.pack_messages_np([buf[off:total]], leaf_capacity=cap)[0][0]
        else:
            got = np.asarray(fused_convert._gather_pack_sha(words, offs, sizes, cap))[:, :, 0]
            want = self._sha_blocks(buf[off:total], cap)
            assert len(sha256.pad_message_np(buf[off:total])) == cap  # the widest row of the widest class
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("length", [8, 9, 10, 11])
    def test_lane_words_of_a_buffer_that_is_not_whole_words(self, length):
        buf = np.arange(2 * length, dtype=np.uint8).reshape(2, length)
        words = fused_convert.lane_words(buf)
        assert words.shape == (2, 3 if length > 8 else 2)
        got = words.view(np.uint8).reshape(2, -1)
        np.testing.assert_array_equal(got[:, :length], buf)
        assert not got[:, length:].any()
        assert int(words[0, 0]) == 0x03020100  # the first byte in the low bits


class TestFusedPackLane:
    def test_pack_layer_byte_identity_vs_hybrid(self):
        """PackOption(backend="fused") must produce byte-identical layer
        blobs and bootstraps to the host lane — the cross-lane invariant
        every other arm holds (tests/test_fast_tar.py)."""
        import io
        import tarfile

        from nydus_snapshotter_tpu.converter.convert import pack_layer
        from nydus_snapshotter_tpu.converter.types import PackOption

        rng = np.random.default_rng(5)
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w") as tf:
            for i in range(24):
                size = int(rng.choice([0, 100, 5000, 80_000, 400_000]))
                data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
                ti = tarfile.TarInfo(f"d/f{i}")
                ti.size = size
                tf.addfile(ti, io.BytesIO(data))
            ti = tarfile.TarInfo("d/link")
            ti.type = tarfile.SYMTYPE
            ti.linkname = "f0"
            tf.addfile(ti)
        tar = buf.getvalue()

        for compressor in ("none", "lz4_block"):
            blob_h, res_h = pack_layer(
                tar,
                PackOption(
                    chunk_size=0x10000, backend="hybrid", compressor=compressor
                ),
            )
            blob_f, res_f = pack_layer(
                tar,
                PackOption(
                    chunk_size=0x10000, backend="fused", compressor=compressor
                ),
            )
            assert blob_h == blob_f, compressor
            assert res_h.bootstrap == res_f.bootstrap, compressor
            assert res_h.blob_id == res_f.blob_id, compressor


class TestFusedBlake3:
    def test_blake3_digests_match_spec(self):
        """blake3 fused lane: device-gathered digests must equal the
        pure-Python spec implementation over the same cuts."""
        from nydus_snapshotter_tpu.utils import blake3 as pyb3

        streams = _corpus(31, [3, 2000, 150_000, 70_000, 1_048_577])
        eng = fused_convert.FusedDeviceEngine(chunk_size=CHUNK, digester="blake3")
        res = eng.process_many(streams)
        # cuts are digester-independent: same oracle as sha256
        oracle = ChunkDigestEngine(
            chunk_size=CHUNK, backend="numpy", digest_backend="numpy"
        )
        want = oracle.process_many(streams)
        for i, (cuts, metas) in enumerate(zip(res.cuts, want)):
            np.testing.assert_array_equal(
                cuts, [m.offset + m.size for m in metas], err_msg=f"stream {i}"
            )
        for s, cuts, digs in zip(streams, res.cuts, res.digests):
            prev = 0
            for cut, d in zip(cuts, digs):
                assert pyb3.blake3(s[prev:cut]) == d
                prev = int(cut)

    def test_pack_layer_blake3_byte_identity_vs_hybrid(self):
        import io
        import tarfile

        from nydus_snapshotter_tpu.converter.convert import pack_layer
        from nydus_snapshotter_tpu.converter.types import PackOption

        rng = np.random.default_rng(37)
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w") as tf:
            for i in range(12):
                size = int(rng.choice([90, 6000, 120_000]))
                data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
                ti = tarfile.TarInfo(f"x/f{i}")
                ti.size = size
                tf.addfile(ti, io.BytesIO(data))
        tar = buf.getvalue()
        kw = dict(chunk_size=0x10000, digester="blake3", compressor="zstd")
        blob_h, res_h = pack_layer(tar, PackOption(backend="hybrid", **kw))
        blob_f, res_f = pack_layer(tar, PackOption(backend="fused", **kw))
        assert blob_h == blob_f
        assert res_h.bootstrap == res_f.bootstrap


class TestFusedRandomizedSoak:
    def test_randomized_corpora_match_oracle(self, oracle):
        """Randomized differential: many small corpora with adversarial
        size mixes (empties, 1-byte, min_size boundaries, window-straddling
        sizes) — cuts and digests must match the numpy oracle on every
        seed."""
        eng = fused_convert.FusedDeviceEngine(chunk_size=CHUNK)
        params = eng.params
        edge_sizes = [
            0, 1, 31, 32, params.min_size - 1, params.min_size,
            params.min_size + 1, params.normal_size, params.max_size,
            params.max_size + 17,
        ]
        for seed in range(6):
            rng = np.random.default_rng(100 + seed)
            sizes = [int(rng.choice(edge_sizes)) for _ in range(4)] + [
                int(rng.integers(1, 300_000)) for _ in range(4)
            ]
            streams = _corpus(200 + seed, sizes)
            res = eng.process_many(streams)
            want = oracle.process_many(streams)
            for i, (cuts, digs, metas) in enumerate(
                zip(res.cuts, res.digests, want)
            ):
                np.testing.assert_array_equal(
                    cuts,
                    [m.offset + m.size for m in metas],
                    err_msg=f"seed {seed} stream {i}",
                )
                assert digs == [m.digest for m in metas], f"seed {seed} stream {i}"

    def test_pack_stream_overflow_falls_back_identically(self, monkeypatch):
        """When the fused lane overflows its candidate capacity mid-pack,
        pack_stream must fall through to the per-file paths and still
        produce the byte-identical blob."""
        import io
        import tarfile

        from nydus_snapshotter_tpu.converter.convert import pack_layer
        from nydus_snapshotter_tpu.converter.types import PackOption

        rng = np.random.default_rng(41)
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w") as tf:
            for i in range(6):
                data = rng.integers(0, 256, 150_000, dtype=np.uint8).tobytes()
                ti = tarfile.TarInfo(f"o/f{i}")
                ti.size = len(data)
                tf.addfile(ti, io.BytesIO(data))
        tar = buf.getvalue()
        blob_h, res_h = pack_layer(
            tar, PackOption(chunk_size=CHUNK, backend="hybrid")
        )
        monkeypatch.setattr(
            fused_convert, "_wcap_for", lambda n, bits, floor=1024: 2
        )
        blob_f, res_f = pack_layer(
            tar, PackOption(chunk_size=CHUNK, backend="fused")
        )
        assert blob_f == blob_h
        assert res_f.bootstrap == res_h.bootstrap

    def test_streaming_pack_fused_backend_identical(self):
        """File-like (streaming) Pack with backend='fused': the fused
        batch lane only serves the in-memory walk, so the streaming path
        must fall back to the engine's windowed boundaries and still
        produce the byte-identical blob."""
        import io
        import tarfile

        from nydus_snapshotter_tpu.converter.convert import Pack
        from nydus_snapshotter_tpu.converter.types import PackOption

        rng = np.random.default_rng(43)
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w") as tf:
            for i in range(5):
                data = rng.integers(0, 256, 200_000, dtype=np.uint8).tobytes()
                ti = tarfile.TarInfo(f"s/f{i}")
                ti.size = len(data)
                tf.addfile(ti, io.BytesIO(data))
        tar = buf.getvalue()

        def pack_with(backend, source):
            out = io.BytesIO()
            res = Pack(out, source, PackOption(chunk_size=CHUNK, backend=backend))
            return out.getvalue(), res

        mem_blob, _ = pack_with("fused", tar)
        stream_blob, _ = pack_with("fused", io.BytesIO(tar))
        hybrid_blob, _ = pack_with("hybrid", io.BytesIO(tar))
        assert mem_blob == stream_blob == hybrid_blob

    def test_pallas_probe_interpret_matches_xla(self):
        """The Pallas DMA-probe lane of pass 2 (used on real TPU) must
        agree with the XLA gather formulation — driven in interpret mode
        on CPU, same discipline as tests/test_probe_pallas.py."""
        streams = _corpus(53, [250_000, 120_000])
        eng = fused_convert.FusedDeviceEngine(chunk_size=CHUNK)
        first = eng.process_many(streams)
        flat = [d for digs in first.digests for d in digs]
        digests_u32 = (
            np.frombuffer(b"".join(flat), dtype=">u4").astype(np.uint32).reshape(-1, 8)
        )
        keys, values = _build_host_tables(digests_u32, 1)
        depth = _table_max_depth(keys, values)
        streams2 = [streams[0], _corpus(59, [90_000])[0]]
        res_xla = eng.process_many(
            streams2, chunk_dict=(keys[0], values[0]), depth=depth,
            probe_kernel="xla",
        )
        res_pl = eng.process_many(
            streams2, chunk_dict=(keys[0], values[0]), depth=depth,
            probe_kernel="pallas-interpret",
        )
        np.testing.assert_array_equal(res_pl.probe, res_xla.probe)
        assert (res_pl.probe[: len(res_pl.digests[0])] > 0).all()


class TestEarlyStart:
    """begin() enqueues the upload and pass 1 on a bare buffer, before any
    file table exists; process_many(Extents, begun=...) waits for them and
    runs the rest. Same cuts, digests and probe as process_many(Extents)
    alone, one entry, one dispatch counted."""

    @staticmethod
    def _counts() -> tuple[float, float, float]:
        disp, _bytes, _stages, fallbacks = fused_convert._counters()
        return disp.value(), fused_convert._early_start_counter().value(), fallbacks.value()

    @pytest.mark.parametrize("room", ["slack", "bytes"])
    @pytest.mark.parametrize("with_dict", [False, True])
    @pytest.mark.parametrize("digester", ["sha256", "blake3"])
    def test_begun_matches_process_many(self, digester, with_dict, room):
        from nydus_snapshotter_tpu import trace

        tar, extents = _edge_tar()
        eng = fused_convert.FusedDeviceEngine(chunk_size=SMALL, digester=digester)
        if room == "bytes":
            data = tar
        else:
            big = fused_convert.zeroed_buffer(fused_convert.padded_length(len(tar), eng.params.max_size))
            big[: len(tar)] = np.frombuffer(tar, dtype=np.uint8)
            data = big[: len(tar)]
        chunk_dict, depth = _every_other_chunk(eng, fused_convert.Extents(data, extents)) if with_dict else (None, 8)
        want = eng.process_many(fused_convert.Extents(data, extents), chunk_dict=chunk_dict, depth=depth)

        before = self._counts()
        with trace.Stages() as stages:
            begun = eng.begin(data, stages)  # no table yet
            assert begun.table is None and begun.n == len(tar) and stages.running == "pack:lane.pass1"
            stages.next("pack:scan")  # the caller's own work, under the device's
            got = eng.process_many(
                fused_convert.Extents(data, extents), chunk_dict=chunk_dict, depth=depth,
                stages=stages, begun=begun,
            )
            # pass 1's operand went when its candidates were on the host; pass 2 read the words
            assert begun.buffer_dev is None and not begun.words_dev.is_deleted()
            begun.close()
        assert [b - a for a, b in zip(before, self._counts())] == [1, 1, 0]
        assert list(stages.seconds) == [f"pack:lane.{s}" for s in ("layout", "h2d", "pass1")] + ["pack:scan"] + [
            f"pack:lane.{s}" for s in ("cand_d2h", "resolve", "plan", "pass2", "digest_d2h")
        ]
        for i, (g, w) in enumerate(zip(got.cuts, want.cuts, strict=True)):
            np.testing.assert_array_equal(g, w, err_msg=f"file {i}")
        assert got.digests == want.digests
        if with_dict:
            np.testing.assert_array_equal(got.probe, want.probe)
            assert (got.probe > 0).any() and (got.probe == 0).any()
        else:
            assert got.probe is None

    @pytest.mark.parametrize("other", ["an equal copy", "a list of the members", "bytes of the array"])
    def test_a_lane_begun_on_another_buffer_is_refused(self, other):
        tar, extents = _edge_tar()
        eng = fused_convert.FusedDeviceEngine(chunk_size=SMALL)
        arr = np.frombuffer(tar, dtype=np.uint8)
        streams = {
            "an equal copy": fused_convert.Extents(arr.copy(), extents),
            "a list of the members": [tar[off : off + size] for off, size in extents],
            "bytes of the array": fused_convert.Extents(tar, extents),
        }[other]
        from nydus_snapshotter_tpu import trace

        before = self._counts()
        with trace.Stages() as stages:
            begun = eng.begin(arr, stages)
            with pytest.raises(ValueError, match="begun on another buffer"):
                eng.process_many(streams, stages=stages, begun=begun)
            begun.close()
        assert self._counts() == before

    def test_a_begun_lane_that_is_dropped_counts_nowhere_and_waits_for_nothing(self):
        from nydus_snapshotter_tpu import trace

        tar, extents = _edge_tar()
        eng = fused_convert.FusedDeviceEngine(chunk_size=SMALL)
        before = self._counts()
        with trace.Stages() as stages:
            begun = eng.begin(tar, stages)
            dev, words_dev, words = begun.buffer_dev, begun.words_dev, begun.words
            assert len(words) == 6 and not dev.is_deleted()
            assert words_dev.dtype == np.uint32 and words_dev.size * 4 == dev.size  # the same bytes, twice
            begun.close()
            begun.close()  # whoever comes second finds nothing left
        assert dev.is_deleted() and words_dev.is_deleted() and all(w.is_deleted() for w in words)
        assert begun.buf is None and begun.buffer_dev is None and begun.words_dev is None and begun.words == ()
        assert self._counts() == before
        # and the next batch is none the worse for it
        got = eng.process_many(fused_convert.Extents(tar, extents))
        assert sum(len(c) for c in got.cuts) > len(extents)

    def test_overflow_is_met_where_the_counts_arrive(self, monkeypatch):
        from nydus_snapshotter_tpu import trace

        monkeypatch.setattr(fused_convert, "_wcap_for", lambda n, bits, floor=1024: 2)
        eng = fused_convert.FusedDeviceEngine(chunk_size=CHUNK)
        data = _corpus(23, [1 << 20])[0]
        before = self._counts()
        with trace.Stages() as stages:
            begun = eng.begin(data, stages)  # enqueues: the counts are not known yet
            with pytest.raises(fused_convert.FusedOverflow, match="exceed caps 2/2"):
                eng.process_many(fused_convert.Extents(data, [(0, len(data))]), stages=stages, begun=begun)
            begun.close()
        assert self._counts() == before  # the caller counts the fallback, not the engine

    @pytest.mark.parametrize("table", [[], [(0, 0), (0, 0)]])
    def test_nothing_to_cut_waits_for_nothing(self, table):
        from nydus_snapshotter_tpu import trace

        eng = fused_convert.FusedDeviceEngine(chunk_size=SMALL)
        for data in (b"", bytes(10_000)):
            before = self._counts()
            with trace.Stages() as stages:
                begun = eng.begin(data, stages)
                assert (begun.buffer_dev is None) == (not data)
                got = eng.process_many(fused_convert.Extents(data, table), stages=stages, begun=begun)
                begun.close()
            assert [list(c) for c in got.cuts] == [[] for _ in table] and got.probe is None
            assert "pack:lane.cand_d2h" not in stages.seconds and self._counts() == before
