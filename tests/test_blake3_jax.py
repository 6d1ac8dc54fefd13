"""Device BLAKE3 kernel differentials (ops/blake3_jax).

The TPU-native digest lane for the real toolchain's default chunk
digester: leaves compress in parallel vector lanes, the tree merges in
log-depth vectorized levels. Oracle: utils/blake3.py (the pure-Python
spec implementation validated against the committed real-fixture
digests). Runs on the virtual CPU mesh (conftest pins jax_platforms=cpu);
chip_smoke.py runs the blake3 lane on the chip.
"""

from __future__ import annotations

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nydus_snapshotter_tpu.ops import blake3_jax as B
from nydus_snapshotter_tpu.utils import blake3 as pyb3


class TestBlake3Jax:
    def test_matches_oracle_across_tree_shapes(self):
        rng = random.Random(3)
        sizes = [0, 1, 63, 64, 65, 1023, 1024, 1025, 2048, 3071, 3072,
                 4096, 5 * 1024 + 7, 65536, (1 << 17) + 13]
        msgs = [bytes(rng.randrange(256) for _ in range(s)) for s in sizes]
        got = B.blake3_many(msgs)
        for s, g, m in zip(sizes, got, msgs):
            assert g == pyb3.blake3(m), s

    def test_known_vector_empty(self):
        assert B.blake3_many([b""])[0].hex().startswith("af1349b9f5f9a1a6")

    def test_capacity_padding_and_batch_pad_rows(self):
        # A mixed batch in one fixed leaf capacity: the pow2-rounded cap
        # and dummy pad rows must not perturb real rows.
        rng = random.Random(9)
        msgs = [bytes(rng.randrange(256) for _ in range(s)) for s in [10, 5000, 70000]]
        blocks, lengths = B.pack_messages_np(msgs, leaf_capacity=96)  # rounds to 128
        assert blocks.shape[1] == 128
        blocks = np.concatenate([blocks, np.zeros((2,) + blocks.shape[1:], np.uint32)])
        lengths = np.concatenate([lengths, np.zeros(2, np.int32)])
        words = np.asarray(
            jax.device_get(B.blake3_batch(jnp.asarray(blocks), jnp.asarray(lengths)))
        )
        for i, m in enumerate(msgs):
            assert B.digest_to_bytes(words[i]) == pyb3.blake3(m)
        # pad rows digest the empty message — defined, not garbage
        assert B.digest_to_bytes(words[3]) == pyb3.blake3(b"")

    def test_capacity_overflow_rejected(self):
        with pytest.raises(ValueError):
            B.pack_messages_np([b"x" * 5000], leaf_capacity=4)

    def test_engine_device_lane(self):
        # ChunkDigestEngine(digester="blake3", digest_backend="jax") routes
        # through the bucketed device kernel; must equal the host lane.
        from nydus_snapshotter_tpu.ops.chunker import ChunkDigestEngine

        rng = random.Random(21)
        data = bytes(rng.randrange(256) for _ in range(3 << 20))
        dev = ChunkDigestEngine(
            backend="hybrid", digester="blake3", digest_backend="jax"
        )
        host = ChunkDigestEngine(backend="hybrid", digester="blake3")
        cuts = dev.boundaries(data)
        got = dev.digests(data, cuts)
        want = host.digests(data, cuts)
        assert got == want
        import hashlib

        arr = np.frombuffer(data, dtype=np.uint8)
        s = 0
        for c, d in zip(cuts, got):
            assert d == pyb3.blake3(data[s : int(c)])
            s = int(c)

    def test_digest_many_device_lane(self):
        from nydus_snapshotter_tpu.ops.chunker import ChunkDigestEngine

        rng = random.Random(17)
        datas = [bytes(rng.randrange(256) for _ in range(s)) for s in [0, 700, 1024, 90000]]
        dev = ChunkDigestEngine(
            backend="hybrid", digester="blake3", digest_backend="jax"
        )
        assert dev.digest_many(datas) == [pyb3.blake3(d) for d in datas]
