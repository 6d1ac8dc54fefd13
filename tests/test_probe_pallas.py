"""Differential tests for the Pallas dict-probe kernel (ops/probe_pallas).

The kernel replaces the XLA gather probe on real TPU hardware; without a
chip in the dev loop it runs here in interpret mode, differentially
against the XLA `_probe_local` oracle and the native host probe — same
discipline as the gear kernel's tests.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nydus_snapshotter_tpu.ops import probe_pallas
from nydus_snapshotter_tpu.parallel.sharded_dict import (
    MAX_PROBE,
    ShardedChunkDict,
    _build_host_tables,
    _probe_local,
    _table_max_depth,
)


def _mk_table(n=20_000, n_shards=1, seed=5):
    rng = np.random.default_rng(seed)
    digests = rng.integers(0, 2**32, (n, 8), dtype=np.uint32)
    keys, values = _build_host_tables(digests, n_shards)
    return digests, keys, values


def _queries(digests, m, seed=9):
    rng = np.random.default_rng(seed)
    q = np.concatenate(
        [
            digests[rng.integers(0, len(digests), m // 2)],
            rng.integers(0, 2**32, (m - m // 2, 8), dtype=np.uint32),
        ]
    )
    rng.shuffle(q)
    return q


class TestProbePallas:
    def test_matches_xla_oracle(self):
        digests, keys, values = _mk_table()
        depth = _table_max_depth(keys, values)
        q = _queries(digests, 1500)
        got = probe_pallas.probe(keys[0], values[0], q, depth, interpret=True)
        cap = keys.shape[1]
        want = np.asarray(
            jax.jit(lambda k, v, qq: _probe_local(k, v, qq, cap, depth))(
                jnp.asarray(keys[0]), jnp.asarray(values[0]), jnp.asarray(q)
            )
        )
        assert (got == want).all()
        assert (got != 0).sum() == 750  # every planted digest found

    def test_chain_window_wrap(self):
        """Queries whose chains start near the table end exercise the
        wrap-free head-replication pad."""
        digests, keys, values = _mk_table(n=3000, seed=11)
        depth = max(_table_max_depth(keys, values), 4)
        cap = keys.shape[1]
        # synthesize queries landing in the last window rows
        occupied = np.nonzero(values[0] != 0)[0]
        tail = occupied[occupied >= cap - probe_pallas.window_slots(depth)]
        if len(tail) == 0:
            pytest.skip("no occupied slot near the table tail for this seed")
        q = keys[0][tail]
        got = probe_pallas.probe(keys[0], values[0], q, depth, interpret=True)
        assert (got == values[0][tail]).all()

    def test_depth_one_and_max(self):
        digests, keys, values = _mk_table(n=500, seed=3)
        q = _queries(digests, 64, seed=4)
        cap = keys.shape[1]
        for depth in (1, 8, MAX_PROBE):
            got = probe_pallas.probe(keys[0], values[0], q, depth, interpret=True)
            want = np.asarray(
                jax.jit(lambda k, v, qq: _probe_local(k, v, qq, cap, depth))(
                    jnp.asarray(keys[0]), jnp.asarray(values[0]), jnp.asarray(q)
                )
            )
            assert (got == want).all(), depth

    def test_sharded_dict_pallas_backend(self):
        """End-to-end through ShardedChunkDict(probe_backend='pallas'):
        multi-shard host partitioning + per-shard kernel launches agree
        with the native host probe."""
        rng = np.random.default_rng(21)
        digests = rng.integers(0, 2**32, (30_000, 8), dtype=np.uint32)
        d_pal = ShardedChunkDict(
            digests, probe_backend="pallas", pallas_interpret=True
        )
        d_host = ShardedChunkDict(digests, probe_backend="host")
        q = _queries(digests, 2048, seed=22)
        a = d_pal.lookup_u32(q)
        b = d_host.lookup_u32(q)
        assert (a == b).all()
        assert (a >= 0).sum() == 1024


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-q"]))
