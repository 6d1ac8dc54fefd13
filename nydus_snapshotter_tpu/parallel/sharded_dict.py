"""HBM-resident sharded chunk dictionary for cross-image dedup.

The reference's dedup dictionary is a bootstrap file the Rust builder re-reads
per conversion (``--chunk-dict bootstrap=…``, pkg/converter/tool/builder.go:
122-123). At registry scale (10k images) the dict outgrows both a host hash
map's latency budget and a single chip's HBM, so here it lives *on device*,
sharded across the mesh:

- **Layout.** Open-addressing table per shard: keys ``uint32[C, 8]`` (raw
  SHA-256 as 8 lanes — exactly the chunk-table digest layout of
  models/bootstrap.py), values ``int32[C]`` (dict chunk index + 1; 0 =
  empty). Shard = ``digest_word0 mod S``, slot base = ``digest_word1 mod C``,
  bounded linear probing.
- **Build.** Host-side, fully vectorized numpy: dedup via a sorted void view
  (first insertion wins), then MAX_PROBE rounds of batched scatter where
  slot conflicts are resolved first-come (np.unique on linearized slots).
  Deterministic and identical to the sequential insertion order.
  ``capacity_factor`` is the probe-latency/HBM dial: device probes pay
  per chain-depth row (the whole window is gathered/DMA'd), so a
  device-probe-heavy deployment builds at factor 8 (~8-deep chains) while
  the memory-lean default of 2 suits the early-exiting host arm.
- **Probe.** Queries arrive row-sharded over the ``data`` axis. Default
  path: bucketed **all_to_all** routing inside ``shard_map`` — each device
  bins its local queries by owning shard into fixed-capacity buckets,
  exchanges buckets over ICI, answers the queries it owns, and routes the
  answers back. ICI traffic is O(M) total instead of the all_gather's
  O(M·S), and per-shard compute is O(M/S). Bucket capacity is 4× the
  uniform expectation (SHA digests are uniform; queries are deduped
  host-side first) — on the (cryptographically unlikely) overflow the probe
  falls back to the dense all_gather+psum path, which is exact for any
  distribution.
- **Persistence.** ``save``/``load`` round-trip the built table through one
  raw header+tables file (mmap'd on load — the table is uniform-random u32,
  where compression bought ~4% for two orders of magnitude of CPU) so the
  dict survives across conversions — the persistent cross-repo dict of
  BASELINE config #5. Legacy ``.npz`` saves still load.
- **Incremental growth.** At registry scale images land continuously; a
  full rebuild per 2M-entry drop costs ~68s (REGISTRY_SCALE). ``insert_u32``
  open-addresses new entries into the spare capacity the build's
  ``capacity_factor`` headroom leaves behind — cost proportional to the
  inserted batch, not the table — falling back to a value-preserving full
  rebuild only on a load-factor breach or a MAX_PROBE chain overflow.
  Previously issued dedup indices NEVER move (``grown_old_indices_stable``):
  values are first-occurrence positions in the concatenated insertion
  sequence, and rebuilds remap stored values instead of renumbering. Every
  mutation batch bumps ``epoch``; ``save`` stamps it and
  ``save_incremental`` appends only the entries a snapshot file is missing
  (compacting to a full rewrite after a rebuild), so converters across
  hosts can load a snapshot, probe locally, and reconcile by epoch
  (``entries_since``).
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from nydus_snapshotter_tpu import failpoint
from nydus_snapshotter_tpu.analysis import runtime as _an
from nydus_snapshotter_tpu.metrics import registry as _metrics
from nydus_snapshotter_tpu.parallel import mesh as mesh_lib

# Longest probe chain the BUILD tolerates before doubling capacity. The
# probe paths bound their loops by the table's actual max chain
# (_table_max_depth, persisted with the table), so a deeper tolerance
# costs probes nothing while halving table bytes whenever chains would
# have crossed the old 32 bound at the current capacity (observed at the
# 32M-entry registry scale: 0.48 load factor -> max chain ~40).
MAX_PROBE = 64
# Chain tolerance for INCREMENTAL inserts. Linear-probing clusters grow
# superlinearly with load: a table built at ~0.48 load has ~40-deep max
# chains, and filling toward 0.6 pushes the longest cluster past the
# build bound — declaring overflow there would silently route every
# sizeable insert batch onto the full-rebuild path (measured: the whole
# incremental win evaporates). Inserts therefore tolerate 4x deeper
# chains before rebuilding; host probes early-exit at the first empty
# slot so the bound itself costs nothing, and the stored max_depth keeps
# the device probe window exact.
INSERT_MAX_PROBE = 256

_FORMAT_VERSION = 1  # legacy .npz container (read-only support)
_RAW_FORMAT_VERSION = 4  # NTPUDICT raw header + dense tables (read-only support)
_RAW_HEADER_FIELDS = 5  # version, n_shards, n_entries, capacity, max_depth
# v5: epoch-stamped base tables + incremental tail of appended entries.
_RAW_FORMAT_VERSION_5 = 5
_RAW_HEADER_FIELDS_V5 = 10  # version, n_shards, n_entries, capacity,
#   max_depth, epoch, rebuild_epoch, n_unique, tail_count, reserved
_TAIL_RECORD_DT = np.dtype([("d", "<u4", 8), ("v", "<u8")])  # digest + stored value

# Growth defaults (config [chunk_dict]: load_factor / headroom).
DEFAULT_LOAD_FACTOR = 0.85
DEFAULT_HEADROOM = 2.0

_INSERT_BATCHES = _metrics.Counter(
    "ntpu_dict_insert_batches_total",
    "Incremental chunk-dict insert batches (epoch bumps)",
)
_INSERT_ENTRIES = _metrics.Counter(
    "ntpu_dict_insert_entries_total",
    "New entries inserted incrementally into chunk-dict tables",
)
_REBUILDS = _metrics.Counter(
    "ntpu_dict_rebuilds_total",
    "Chunk-dict full rebuilds (load-factor breach or chain overflow)",
)


class DictBuildError(RuntimeError):
    pass


class DictEpochError(RuntimeError):
    """Requested epoch predates the last rebuild/compaction: the caller
    holds indices the journal can no longer replay and must full-resync."""


def _build_host_tables(
    digests: np.ndarray, n_shards: int, capacity_factor: float = 2.0
) -> tuple[np.ndarray, np.ndarray]:
    """Deterministic vectorized build → (keys u32[S,C,8], values i32[S,C]).

    First-insertion-wins without any global sort: entries march down their
    probe chains in lockstep rounds. Per round, an entry whose candidate
    slot holds its own digest is a duplicate and is dropped; contenders for
    one free slot are resolved first-come via a reverse-order scatter (numpy
    duplicate-index scatter keeps the last write, so scattering positions in
    reverse makes the earliest entry win). Duplicates that lose a slot race
    to their own digest land later in the probe chain, where lookups (which
    take the first match in chain order) never reach them — value semantics
    stay "index of first occurrence".
    """
    digests = np.ascontiguousarray(digests, dtype=np.uint32)
    n = len(digests)
    shard_of = digests[:, 0] % np.uint32(n_shards) if n else np.zeros(0, np.uint32)
    max_count = int(np.bincount(shard_of, minlength=n_shards).max()) if n else 0
    cap = max(64, 1 << int(np.ceil(np.log2(max(1, capacity_factor * max_count)))))

    from nydus_snapshotter_tpu.ops import native_cdc

    if native_cdc.dict_build_available():
        while True:
            keys = np.empty((n_shards, cap, 8), dtype=np.uint32)
            keys.fill(0)
            values = np.empty((n_shards, cap), dtype=np.int32)
            values.fill(0)
            if native_cdc.dict_build_native(
                digests, n_shards, cap, MAX_PROBE, keys.reshape(-1, 8), values.reshape(-1)
            ):
                return keys, values
            if cap > 1 << 28:
                raise DictBuildError("chunk dict table grew beyond 2^28 slots")
            cap *= 2

    shard_of32 = shard_of.astype(np.int32)
    base_word = digests[:, 1].astype(np.int32) if n else np.zeros(0, np.int32)
    while True:
        # fill() instead of np.zeros: pre-faulting the pages up front turns
        # the first round's random writes from a page-fault storm (~25x
        # slower, measured) into plain stores.
        keys = np.empty((n_shards, cap, 8), dtype=np.uint32)
        keys.fill(0)
        values = np.empty((n_shards, cap), dtype=np.int32)
        values.fill(0)
        flat_keys = keys.reshape(-1, 8)
        flat_vals = values.reshape(-1)
        first_writer = np.full(n_shards * cap, -1, dtype=np.int32)
        remaining = np.arange(n, dtype=np.int32)
        shard_lin = shard_of32 * np.int32(cap)
        for j in range(MAX_PROBE):
            if not len(remaining):
                break
            lin = shard_lin[remaining] + ((base_word[remaining] + np.int32(j)) & np.int32(cap - 1))
            if j == 0:
                # The table is empty on the first round: every slot is free,
                # nothing can be a duplicate — skip the 32-byte key gather.
                cand, cand_lin = remaining, lin
                dup_idx = remaining[:0]
            else:
                occupant = flat_vals[lin]
                free = occupant == 0
                dup = ~free & (flat_keys[lin] == digests[remaining]).all(axis=1)
                cand = remaining[free]
                cand_lin = lin[free]
                dup_idx = remaining[dup]
            # First-come-per-slot via reverse-order scatter (numpy keeps the
            # last write for duplicate indices, so scattering in reverse
            # records the earliest contender). ``cand`` is ascending, so the
            # winner set stays ascending — the digest gather below streams
            # sequentially, which on this memory-bound loop beats any
            # sort-based scheme.
            first_writer[cand_lin[::-1]] = cand[::-1]
            win_mask = first_writer[cand_lin] == cand
            winners = cand[win_mask]
            win_lin = cand_lin[win_mask]
            flat_keys[win_lin] = digests[winners]
            flat_vals[win_lin] = winners + np.int32(1)
            first_writer[cand_lin] = -1  # reset only the touched cells
            drop = np.zeros(n, dtype=bool)
            drop[winners] = True
            drop[dup_idx] = True
            remaining = remaining[~drop[remaining]]
        if not len(remaining):
            return keys, values
        if cap > 1 << 28:
            raise DictBuildError("chunk dict table grew beyond 2^28 slots")
        cap *= 2


def _table_max_depth(keys: np.ndarray, values: np.ndarray) -> int:
    """Longest probe chain actually present in the built table. The probe
    only ever needs this many rounds (first-match-in-chain semantics), and
    it is typically ~4-8 at the 2x capacity factor — bounding the device
    probe loop by it instead of MAX_PROBE is a direct multiplier on probe
    throughput."""
    cap = keys.shape[1]
    flat_v = values.reshape(-1)
    occ = flat_v != 0
    if not occ.any():
        return 1
    occ_keys = keys.reshape(-1, 8)[occ]
    slots = np.nonzero(occ)[0] % cap
    base = occ_keys[:, 1] & np.uint32(cap - 1)
    depth = (slots - base) & np.uint32(cap - 1)
    return int(depth.max()) + 1


def _probe_local(
    k: jax.Array, v: jax.Array, q: jax.Array, cap: int, depth: int = MAX_PROBE
) -> jax.Array:
    """Probe queries against one shard's table: q u32[M,8] -> i32[M].

    One fused gather over the whole chain window (u32[M, D, 8]) instead of
    D sequential row gathers — XLA vectorizes a single big gather far
    better, and `depth` comes from the table itself (_table_max_depth)."""
    slot0 = q[:, 1] & np.uint32(cap - 1)
    slots = (slot0[:, None] + np.arange(depth, dtype=np.uint32)) & np.uint32(
        cap - 1
    )  # [M, D]
    cand_keys = k[slots]  # u32[M, D, 8]
    cand_vals = v[slots]  # i32[M, D]
    match = jnp.all(cand_keys == q[:, None, :], axis=2) & (cand_vals != 0)
    hit = jnp.argmax(match, axis=1)  # first True (argmax on bool)
    found = jnp.take_along_axis(cand_vals, hit[:, None], axis=1)[:, 0]
    return jnp.where(jnp.any(match, axis=1), found, 0)


@functools.partial(jax.jit, static_argnames=("n_shards", "mesh", "depth"))
def _probe_sharded(keys, values, queries, n_shards: int, mesh, depth: int = MAX_PROBE):
    """Dense fallback probe (all_gather + psum): exact for any query
    distribution; ICI/compute cost O(M·S). queries u32[M,8] -> i32[M]."""
    cap = keys.shape[1]

    def shard_fn(k, v, q):
        # k: u32[1,C,8]  v: i32[1,C]  q: u32[M/S,8] (this device's rows)
        k, v = k[0], v[0]
        shard_id = jax.lax.axis_index(mesh_lib.AXIS_DATA)
        allq = jax.lax.all_gather(q, mesh_lib.AXIS_DATA, tiled=True)  # u32[M,8]
        belongs = (allq[:, 0] % np.uint32(n_shards)) == shard_id.astype(jnp.uint32)
        found = _probe_local(k, v, allq, cap, depth)
        return jnp.where(belongs, found, 0)

    partial_answers = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            PartitionSpec(mesh_lib.AXIS_DATA),
            PartitionSpec(mesh_lib.AXIS_DATA),
            PartitionSpec(mesh_lib.AXIS_DATA),
        ),
        out_specs=PartitionSpec(mesh_lib.AXIS_DATA),
    )(keys, values, queries)
    # Each query was answered only by its owning shard; sum the per-shard
    # partial answer vectors (all other shards contributed 0).
    return jnp.sum(partial_answers.reshape(n_shards, -1), axis=0)


def _bucket_capacity(m_local: int, n_shards: int) -> int:
    """Fixed per-(device, target-shard) bucket size: 4x the uniform
    expectation plus headroom."""
    return int(4 * ((m_local + n_shards - 1) // n_shards) + 8)


@functools.partial(jax.jit, static_argnames=("n_shards", "mesh", "depth"))
def _probe_routed(keys, values, queries, n_shards: int, mesh, depth: int = MAX_PROBE):
    """all_to_all probe: route each query to its owning shard, answer
    locally, route answers back. Returns (answers i32[M], overflowed bool[S])
    — when any bucket overflowed its capacity the answers are incomplete and
    the caller must fall back to _probe_sharded."""
    cap = keys.shape[1]
    m_local = queries.shape[0] // n_shards
    bucket_cap = _bucket_capacity(m_local, n_shards)
    axis = mesh_lib.AXIS_DATA

    def shard_fn(k, v, q):
        k, v = k[0], v[0]
        target = (q[:, 0] % np.uint32(n_shards)).astype(jnp.int32)  # [m_local]
        # Rank of each query within its target bucket (stable, by position):
        # one-hot cumulative count.
        onehot = jax.nn.one_hot(target, n_shards, dtype=jnp.int32)  # [m, S]
        rank = (jnp.cumsum(onehot, axis=0) - onehot)[
            jnp.arange(m_local), target
        ]  # occurrences of target before each row
        overflow = jnp.any(rank >= bucket_cap)
        ok = rank < bucket_cap
        slot = jnp.where(ok, target * bucket_cap + rank, n_shards * bucket_cap)
        # Scatter queries (plus a validity lane) into the padded send buffer;
        # one spill row absorbs overflowing writes.
        send = jnp.zeros((n_shards * bucket_cap + 1, 9), dtype=jnp.uint32)
        payload = jnp.concatenate([q, jnp.ones((m_local, 1), jnp.uint32)], axis=1)
        send = send.at[slot].set(payload)[:-1].reshape(n_shards, bucket_cap, 9)
        recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0, tiled=True)
        rq = recv.reshape(-1, 9)
        found = _probe_local(k, v, rq[:, :8], cap, depth) * rq[:, 8].astype(jnp.int32)
        back = jax.lax.all_to_all(
            found.reshape(n_shards, bucket_cap), axis, split_axis=0, concat_axis=0, tiled=True
        ).reshape(-1)
        # Gather each local query's answer from its (target, rank) cell.
        ans = jnp.where(ok, back[jnp.clip(slot, 0, n_shards * bucket_cap - 1)], 0)
        return ans, jnp.full((1,), overflow)

    answers, overflowed = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            PartitionSpec(axis),
            PartitionSpec(axis),
            PartitionSpec(axis),
        ),
        out_specs=(PartitionSpec(axis), PartitionSpec(axis)),
    )(keys, values, queries)
    return answers, overflowed


class ShardedChunkDict:
    """Device-resident dedup dictionary, one shard per mesh device."""

    pallas_interpret = False  # load()/copy() build without __init__

    def __init__(
        self,
        digests_u32: np.ndarray,
        mesh=None,
        capacity_factor: float = DEFAULT_HEADROOM,
        probe_backend: str = "auto",
        load_factor: float = DEFAULT_LOAD_FACTOR,
        pallas_interpret: bool = False,
    ):
        if probe_backend not in ("auto", "device", "host", "pallas"):
            raise ValueError(f"unknown probe backend {probe_backend!r}")
        if not 0.0 < load_factor < 1.0:
            raise ValueError(f"load_factor must be in (0, 1), got {load_factor}")
        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh()
        self.n_shards = int(np.prod(list(self.mesh.shape.values())))
        self.probe_backend = probe_backend
        # Pallas interpret mode is a test arm: only a caller that asks
        # for it gets it (probe_backend="pallas" compiles for the chip)
        self.pallas_interpret = pallas_interpret
        self.capacity_factor = capacity_factor
        self.load_factor = load_factor
        self._init_growth_state()
        digests_u32 = np.asarray(digests_u32, dtype=np.uint32).reshape(-1, 8)
        self.n_entries = len(digests_u32)
        keys, values = _build_host_tables(digests_u32, self.n_shards, capacity_factor)
        self._put_tables(keys, values)
        self._n_unique = int(np.count_nonzero(self._host_values))

    def _init_growth_state(self) -> None:
        # Epoch bumps once per mutation batch; rebuild_epoch marks the last
        # compaction point (journal entries before it are folded into the
        # base table and can no longer be replayed individually).
        self.epoch = 0
        self.rebuild_epoch = 0
        # (epoch, digests u32[k,8], stored values i64[k]) per insert batch
        # since the last rebuild — feeds save_incremental/entries_since.
        self._journal: list[tuple[int, np.ndarray, np.ndarray]] = []
        self._n_unique: "int | None" = None  # occupied slots (lazy for v4 loads)
        self._mu = _an.make_lock("dict.mutate")  # serializes mutation; probes are lock-free
        # Lockset annotation: entry counts / epoch / journal only mutate
        # under _mu. The probe TABLES are deliberately not annotated:
        # they are lock-free by design (key-before-value release stores,
        # verified under TSan in tests/test_native_sanitizers.py).
        self._meta_shared = _an.shared("dict.meta")

    def _put_tables(
        self, keys: np.ndarray, values: np.ndarray, max_depth: "int | None" = None
    ) -> None:
        self.capacity = keys.shape[1]
        self.max_depth = (
            max_depth if max_depth is not None else _table_max_depth(keys, values)
        )
        # Host arrays back the native probe arm and save(); the device
        # copies serve the sharded all_to_all probe and are staged LAZILY —
        # the single-chip host-probe default (and an mmap'd load()) must
        # not pay a full-table device transfer it never uses.
        self._host_keys = np.ascontiguousarray(keys, dtype=np.uint32)
        self._host_values = np.ascontiguousarray(values, dtype=np.int32)
        self._keys = None
        self._values = None
        # One-tuple snapshot read by every probe path: a concurrent
        # rebuild/insert publishes (keys, values, capacity, depth) together,
        # so a probe never pairs a new capacity with old tables.
        self._tables = (self._host_keys, self._host_values, self.capacity, self.max_depth)

    def _device_tables(self):
        if self._keys is None:
            shard_sharding = NamedSharding(self.mesh, PartitionSpec(mesh_lib.AXIS_DATA))
            self._keys = jax.device_put(self._host_keys, shard_sharding)
            self._values = jax.device_put(self._host_values, shard_sharding)
        return self._keys, self._values

    def _use_host_probe(self) -> bool:
        """Crossover policy: the device probe exists for dicts sharded over a
        real multi-chip mesh (HBM capacity + ICI all_to_all); on a single
        device XLA's gather executes element-serially (~1 µs/element measured
        on v5e), so the native host probe wins outright."""
        from nydus_snapshotter_tpu.ops import native_cdc

        if self.probe_backend == "host":
            return True
        if self.probe_backend in ("device", "pallas"):
            return False
        return self.n_shards == 1 and native_cdc.dict_probe_available()

    # -- incremental growth --------------------------------------------------

    def insert_digests(self, digests: list[bytes]) -> np.ndarray:
        """Insert raw 32-byte digests; returns their dict indices."""
        if not digests:
            return np.zeros(0, dtype=np.int64)
        arr = np.frombuffer(b"".join(digests), dtype="<u4").reshape(len(digests), 8)
        return self.insert_u32(arr)

    def insert_u32(self, digests_u32: np.ndarray) -> np.ndarray:
        """Insert a batch of digests into spare capacity: u32[M,8] ->
        int64[M] dict indices.

        Semantics are exactly a fresh build over the concatenated insertion
        sequence: a digest already in the dict (or earlier in this batch)
        resolves to its first-occurrence index; genuinely new digests get
        consecutive indices continuing ``n_entries``. Cost is proportional
        to the batch (probe + scatter along each new entry's chain), not
        the table; a load-factor breach or MAX_PROBE overflow triggers a
        value-preserving rebuild with ``capacity_factor`` headroom. Bumps
        ``epoch`` once. Concurrent probes are safe: slots are published
        key-before-value and old entries never move outside a rebuild,
        which swaps the whole table snapshot atomically.
        """
        digests_u32 = np.asarray(digests_u32, dtype=np.uint32).reshape(-1, 8)
        n = len(digests_u32)
        if n == 0:
            return np.zeros(0, dtype=np.int64)
        failpoint.hit("dict.insert")
        with self._mu:
            self._meta_shared.write()
            base = self.n_entries
            if base + n + 1 >= 1 << 31:
                raise DictBuildError("chunk dict exceeds int32 index space")
            fast = self._insert_fast(digests_u32, base)
            if fast is not None:
                return fast
            # Batch-internal first occurrence (value semantics = index of
            # first occurrence in the concatenated sequence).
            void = np.ascontiguousarray(digests_u32).view(np.dtype((np.void, 32)))[:, 0]
            _, first, inverse = np.unique(void, return_index=True, return_inverse=True)
            uniq = digests_u32[first]
            existing = self.lookup_u32(uniq)  # int64, -1 = absent
            new_mask = existing < 0
            assigned = np.where(new_mask, base + first, existing)
            self.epoch += 1
            _INSERT_BATCHES.inc()
            if new_mask.any():
                ins_rows = np.sort(first[new_mask])
                ins_digests = np.ascontiguousarray(digests_u32[ins_rows])
                ins_values = (base + ins_rows + 1).astype(np.int64)  # stored form
                rebuilt = self._insert_entries(ins_digests, ins_values)
                if not rebuilt:
                    self._journal.append((self.epoch, ins_digests, ins_values))
                _INSERT_ENTRIES.inc(len(ins_rows))
            self.n_entries = base + n
            return assigned[inverse].astype(np.int64)

    def _insert_fast(self, digests_u32: np.ndarray, base: int) -> "np.ndarray | None":
        """One fused native pass over the batch (probe-or-insert per
        entry, in order): no host-side dedup sort, no separate lookup —
        at the 32M-entry scale those cost more than the insert itself.
        Returns the assigned indices, or None when the arm is
        unavailable/ineligible (caller runs the vectorized path; a
        mid-batch chain overflow also returns None, and the entries the
        pass already placed carry their FINAL values, so the fallback
        resolves them as ordinary hits — idempotent by construction).
        Caller holds ``_mu``."""
        from nydus_snapshotter_tpu.ops import native_cdc

        n = len(digests_u32)
        if not native_cdc.dict_upsert_available() or self.n_entries == 0:
            return None
        if self._ensure_unique_count() + n > int(
            self.load_factor * self.n_shards * self.capacity
        ):
            return None  # worst-case (all new) breaches: take the slow path
        if not self._host_keys.flags.writeable:
            keys = np.array(self._host_keys)  # mmap'd load: copy-on-insert
            values = np.array(self._host_values)
            self._host_keys, self._host_values = keys, values
            self._tables = (keys, values, self.capacity, self.max_depth)
        res = native_cdc.dict_upsert_native(
            np.ascontiguousarray(digests_u32), base,
            self.n_shards, self.capacity, INSERT_MAX_PROBE,
            self._host_keys.reshape(-1, 8), self._host_values.reshape(-1),
        )
        if res is None:
            return None
        depth, n_new, assigned = res
        self.epoch += 1
        _INSERT_BATCHES.inc()
        if n_new:
            new_mask = assigned == (base + np.arange(n, dtype=np.int64))
            ins_digests = np.ascontiguousarray(digests_u32[new_mask])
            ins_values = assigned[new_mask] + 1  # stored (+1) form
            self._journal.append((self.epoch, ins_digests, ins_values))
            _INSERT_ENTRIES.inc(n_new)
            self._n_unique = self._ensure_unique_count() + n_new
            if depth > self.max_depth:
                self.max_depth = depth
            self._keys = None  # device copies restage on next device probe
            self._values = None
            self._tables = (
                self._host_keys, self._host_values, self.capacity, self.max_depth,
            )
        self.n_entries = base + n
        return assigned

    def _ensure_unique_count(self) -> int:
        if self._n_unique is None:  # legacy v4 load: count once, lazily
            self._n_unique = int(np.count_nonzero(self._host_values))
        return self._n_unique

    def _insert_entries(self, digests: np.ndarray, stored_values: np.ndarray) -> bool:
        """Place unique, absent digests with explicit stored values (+1
        form). Returns True when the batch forced a full rebuild. Caller
        holds ``_mu`` (or is still constructing the instance)."""
        k = len(digests)
        if k == 0:
            return False
        if not self._host_keys.flags.writeable:
            # mmap'd load: copy-on-first-insert (probes before any insert
            # keep the lazy page-faulting mmap).
            keys = np.array(self._host_keys)
            values = np.array(self._host_values)
            self._host_keys, self._host_values = keys, values
            self._tables = (keys, values, self.capacity, self.max_depth)
        cap = self.capacity
        if self._ensure_unique_count() + k > int(
            self.load_factor * self.n_shards * cap
        ):
            self._rebuild(digests, stored_values)
            return True
        flat_keys = self._host_keys.reshape(-1, 8)
        flat_vals = self._host_values.reshape(-1)
        from nydus_snapshotter_tpu.ops import native_cdc

        if native_cdc.dict_insert_available():
            # Sequential native insert: ~0.3 µs/entry of pure chain-walk —
            # the lockstep numpy rounds below pay several table-sized
            # gathers of cache misses per round and lose ~10x on the
            # memory-bound path (same story as the build arm).
            depth = native_cdc.dict_insert_native(
                np.ascontiguousarray(digests),
                np.ascontiguousarray(stored_values.astype(np.int32)),
                self.n_shards, cap, INSERT_MAX_PROBE, flat_keys, flat_vals,
            )
            if depth < 0:
                # Chain overflow: fold the whole batch into a rebuild (the
                # already-placed prefix is in the table; the build's
                # first-wins dedup drops those duplicates harmlessly).
                self._rebuild(digests, stored_values)
                return True
            self._n_unique = self._ensure_unique_count() + k
            if depth > self.max_depth:
                self.max_depth = depth
            self._keys = None
            self._values = None
            self._tables = (self._host_keys, self._host_values, cap, self.max_depth)
            return False
        shard_lin = (digests[:, 0] % np.uint32(self.n_shards)).astype(np.int64) * cap
        base_word = digests[:, 1].astype(np.int64)
        vals_i32 = stored_values.astype(np.int32)
        remaining = np.arange(k, dtype=np.int64)
        depth_reached = 0
        for j in range(INSERT_MAX_PROBE):
            if not len(remaining):
                break
            lin = shard_lin[remaining] + ((base_word[remaining] + j) & (cap - 1))
            free = flat_vals[lin] == 0
            cand = remaining[free]
            cand_lin = lin[free]
            # Earliest contender per slot: np.unique keeps the smallest
            # input index per duplicate value, and ``cand`` is ascending —
            # O(batch log batch), never O(table) (insert-proportional cost).
            win_lin, u_idx = np.unique(cand_lin, return_index=True)
            winners = cand[u_idx]
            # Publish key before value: a concurrent probe seeing the key
            # with value 0 treats the slot as empty (linearizes before the
            # insert); value-first could surface a hit with a torn key.
            flat_keys[win_lin] = digests[winners]
            flat_vals[win_lin] = vals_i32[winners]
            if len(winners):
                depth_reached = j + 1
            done = np.zeros(k, dtype=bool)
            done[winners] = True
            remaining = remaining[~done[remaining]]
        if len(remaining):
            # Chain overflow: fold the stragglers into a rebuild (the
            # already-placed part of the batch is in the table and is
            # collected by the rebuild's value-ordered scan).
            self._rebuild(digests[remaining], stored_values[remaining])
            return True
        self._n_unique = self._ensure_unique_count() + k
        if depth_reached > self.max_depth:
            self.max_depth = depth_reached
        self._keys = None  # device copies restage on next device probe
        self._values = None
        self._tables = (self._host_keys, self._host_values, cap, self.max_depth)
        return False

    def _rebuild(
        self,
        extra_digests: "np.ndarray | None" = None,
        extra_values: "np.ndarray | None" = None,
    ) -> None:
        """Value-preserving full rebuild with ``capacity_factor`` headroom.

        Stored values are first-occurrence indices and MUST survive
        (``grown_old_indices_stable``): the fresh build assigns positional
        values over the value-ordered digest list, which are then remapped
        back onto the original stored values. Compaction point: the journal
        resets and ``rebuild_epoch`` advances to the current epoch.
        """
        failpoint.hit("dict.rebuild")
        _REBUILDS.inc()
        flat_v = self._host_values.reshape(-1)
        occ = flat_v != 0
        digs = self._host_keys.reshape(-1, 8)[occ]
        vals = flat_v[occ].astype(np.int64)
        if extra_digests is not None and len(extra_digests):
            digs = np.concatenate([digs, extra_digests])
            vals = np.concatenate([vals, np.asarray(extra_values, dtype=np.int64)])
        order = np.argsort(vals, kind="stable")
        digs = np.ascontiguousarray(digs[order])
        vals = vals[order]
        keys, values = _build_host_tables(digs, self.n_shards, self.capacity_factor)
        # Rebuilt values index into ``digs``; remap onto the stored values.
        orig = np.concatenate([[0], vals]).astype(np.int32)
        self._put_tables(keys, orig[values.reshape(-1)].reshape(values.shape))
        self._n_unique = len(digs)
        self._journal = []
        self.rebuild_epoch = self.epoch

    def entries_since(self, since_epoch: int) -> tuple[np.ndarray, np.ndarray, int]:
        """Journal replay for epoch reconciliation: entries inserted after
        ``since_epoch`` as (digests u32[k,8], indices int64[k], epoch).
        Raises :class:`DictEpochError` when the epoch predates the last
        rebuild (the journal was compacted; caller must full-resync)."""
        with self._mu:
            self._meta_shared.read()
            if since_epoch < self.rebuild_epoch:
                raise DictEpochError(
                    f"epoch {since_epoch} predates last rebuild "
                    f"(epoch {self.rebuild_epoch}); reload a full snapshot"
                )
            batches = [(d, v) for e, d, v in self._journal if e > since_epoch]
            if not batches:
                return (
                    np.zeros((0, 8), dtype=np.uint32),
                    np.zeros(0, dtype=np.int64),
                    self.epoch,
                )
            digs = np.concatenate([d for d, _ in batches])
            vals = np.concatenate([v for _, v in batches]) - 1  # stored -> index
            return digs, vals, self.epoch

    def copy(self) -> "ShardedChunkDict":
        """Deep copy of tables + growth state (shared mesh). Used by tools
        that race incremental growth against rebuilds on equal footing."""
        with self._mu:
            other = self.__class__.__new__(self.__class__)
            other.mesh = self.mesh
            other.n_shards = self.n_shards
            other.probe_backend = self.probe_backend
            other.capacity_factor = self.capacity_factor
            other.load_factor = self.load_factor
            other._init_growth_state()
            other.epoch = self.epoch
            other.rebuild_epoch = self.rebuild_epoch
            other._journal = [(e, d.copy(), v.copy()) for e, d, v in self._journal]
            other._n_unique = self._n_unique
            other.n_entries = self.n_entries
            other._put_tables(
                self._host_keys.copy(), self._host_values.copy(), self.max_depth
            )
            return other

    def fused_probe_tables(self) -> tuple[np.ndarray, np.ndarray, int, int]:
        """(keys u32[C,8], values i32[C], depth, epoch) of the single shard,
        for ops/fused_convert's pass-2 probe lane. The epoch lets the fused
        engine invalidate padded/staged device copies when incremental
        inserts mutate these arrays in place (identity caching alone would
        serve stale probes)."""
        if self.n_shards != 1:
            raise DictBuildError(
                f"fused probe wants a single-shard dict, have {self.n_shards}"
            )
        tables = self._tables
        cached = getattr(self, "_fused_views", None)
        if cached is None or cached[0] is not tables:
            # keys[0] mints a fresh view object per call; cache the views
            # per published snapshot so the fused engine's identity-keyed
            # staging cache can hit across dispatches.
            cached = (tables, tables[0][0], tables[1][0])
            self._fused_views = cached
        return cached[1], cached[2], tables[3], self.epoch

    # -- persistence --------------------------------------------------------
    #
    # Dense raw format: fixed header (incl. max_depth, so loading never
    # rescans the table) + both tables as raw bytes. The table is
    # uniform-random u32 (SHA words) — compression buys ~4% for two
    # orders of magnitude of CPU (np.savez_compressed measured 158 s
    # save / 78 s load on the 32M-entry table, REGISTRY_SCALE r3). Save
    # is one sequential disk-bound write; load is an mmap whose pages
    # fault in as probes touch them. Legacy .npz files (format 1) still
    # load.

    _RAW_MAGIC = b"NTPUDICT"

    def _header_bytes(self, tail_count: int) -> bytes:
        return self._RAW_MAGIC + np.asarray(
            [
                _RAW_FORMAT_VERSION_5, self.n_shards, self.n_entries,
                self.capacity, self.max_depth, self.epoch, self.rebuild_epoch,
                self._ensure_unique_count(), tail_count, 0,
            ],
            dtype=np.uint64,
        ).tobytes()

    def save(self, path: str) -> None:
        """Persist the full table, epoch-stamped (reload with ``load`` — no
        rebuild). The file carries zero tail entries: it IS the compaction
        ``save_incremental`` appends against."""
        with self._mu:
            with open(path, "wb") as f:
                f.write(self._header_bytes(0))
                self._host_keys.tofile(f)
                self._host_values.tofile(f)

    def save_incremental(self, path: str) -> dict:
        """Refresh a saved snapshot by appending only the entries it lacks.

        Appends the journal batches newer than the file's epoch as tail
        records (cost proportional to the inserted entries) and re-stamps
        the header. Falls back to a full rewrite — compaction — when the
        base table was rebuilt since the file was written (the layout
        changed), the file belongs to a different table shape, or the file
        does not exist. Returns ``{"mode": "append"|"full", "appended": k}``.
        """
        import os as _os

        with self._mu:
            hdr = self._read_v5_header(path)
            compatible = (
                hdr is not None
                and hdr["n_shards"] == self.n_shards
                and hdr["capacity"] == self.capacity
                and hdr["rebuild_epoch"] == self.rebuild_epoch
                and hdr["epoch"] <= self.epoch
            )
            if not compatible:
                pass  # fall through to the full rewrite below
            else:
                pending = [
                    (d, v) for e, d, v in self._journal if e > hdr["epoch"]
                ]
                k = sum(len(d) for d, _ in pending)
                expect = (
                    8 + 8 * _RAW_HEADER_FIELDS_V5
                    + self.n_shards * self.capacity * 36
                    + hdr["tail_count"] * _TAIL_RECORD_DT.itemsize
                )
                if _os.path.getsize(path) == expect:
                    with open(path, "r+b") as f:
                        # Tail first, header last: a torn append leaves the
                        # old header, whose tail_count ignores the partial
                        # records past the end it describes.
                        f.seek(0, 2)
                        for digs, vals in pending:
                            rec = np.zeros(len(digs), dtype=_TAIL_RECORD_DT)
                            rec["d"] = digs
                            rec["v"] = vals.astype(np.uint64)
                            rec.tofile(f)
                        f.seek(0)
                        f.write(self._header_bytes(hdr["tail_count"] + k))
                    return {"mode": "append", "appended": k}
        self.save(path)
        return {"mode": "full", "appended": self.n_entries}

    @classmethod
    def _read_v5_header(cls, path: str) -> "dict | None":
        try:
            with open(path, "rb") as f:
                magic = f.read(8)
                raw = f.read(8 * _RAW_HEADER_FIELDS_V5)
        except OSError:
            return None
        if magic != cls._RAW_MAGIC or len(raw) != 8 * _RAW_HEADER_FIELDS_V5:
            return None
        vals = np.frombuffer(raw, dtype=np.uint64)
        if int(vals[0]) != _RAW_FORMAT_VERSION_5:
            return None
        names = (
            "version", "n_shards", "n_entries", "capacity", "max_depth",
            "epoch", "rebuild_epoch", "n_unique", "tail_count",
        )
        return {k: int(v) for k, v in zip(names, vals)}

    @classmethod
    def load(
        cls,
        path: str,
        mesh=None,
        probe_backend: str = "auto",
        capacity_factor: float = DEFAULT_HEADROOM,
        load_factor: float = DEFAULT_LOAD_FACTOR,
    ) -> "ShardedChunkDict":
        import os as _os

        with open(path, "rb") as f:
            magic = f.read(8)
        tail = None
        epoch = rebuild_epoch = 0
        n_unique: "int | None" = None
        if magic == cls._RAW_MAGIC:
            hdr5 = cls._read_v5_header(path)
            if hdr5 is not None:
                n_shards, n_entries = hdr5["n_shards"], hdr5["n_entries"]
                cap, max_depth = hdr5["capacity"], hdr5["max_depth"]
                epoch, rebuild_epoch = hdr5["epoch"], hdr5["rebuild_epoch"]
                base = 8 + 8 * _RAW_HEADER_FIELDS_V5
                tail_count = hdr5["tail_count"]
                n_unique = hdr5["n_unique"] - tail_count  # base-table occupancy
                tail_base = base + n_shards * cap * 36
                if _os.path.getsize(path) < tail_base + tail_count * _TAIL_RECORD_DT.itemsize:
                    raise DictBuildError("chunk dict file truncated")
                if tail_count:
                    tail = np.fromfile(
                        path, dtype=_TAIL_RECORD_DT, count=tail_count, offset=tail_base
                    )
            else:
                hdr = np.fromfile(
                    path, dtype=np.uint64, count=_RAW_HEADER_FIELDS, offset=8
                )
                if len(hdr) != _RAW_HEADER_FIELDS:
                    raise DictBuildError("chunk dict file truncated (short header)")
                version, n_shards, n_entries, cap, max_depth = (int(x) for x in hdr)
                if version != _RAW_FORMAT_VERSION:
                    raise DictBuildError(
                        f"chunk dict file format {version} != {_RAW_FORMAT_VERSION}"
                    )
                base = 8 + 8 * _RAW_HEADER_FIELDS
                if _os.path.getsize(path) < base + n_shards * cap * 36:
                    raise DictBuildError("chunk dict file truncated")
            keys = np.memmap(
                path, dtype=np.uint32, mode="r", offset=base,
                shape=(n_shards, cap, 8),
            )
            values = np.memmap(
                path, dtype=np.int32, mode="r",
                offset=base + keys.nbytes, shape=(n_shards, cap),
            )
            loaded_depth = int(max_depth)
        else:
            with np.load(path) as z:
                if int(z["format_version"]) != _FORMAT_VERSION:
                    raise DictBuildError(
                        f"chunk dict file format {int(z['format_version'])} != {_FORMAT_VERSION}"
                    )
                keys, values = z["keys"], z["values"]
                n_shards, n_entries = int(z["n_shards"]), int(z["n_entries"])
            loaded_depth = None  # legacy files carry no depth: rescan
        self = cls.__new__(cls)
        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh()
        self.n_shards = int(np.prod(list(self.mesh.shape.values())))
        self.probe_backend = probe_backend
        self.capacity_factor = capacity_factor
        self.load_factor = load_factor
        self._init_growth_state()
        if self.n_shards != n_shards:
            # Table shard count is baked into the layout; rebuild for the new
            # mesh from the stored keys (drop empties, first-wins order by
            # stored value = original insertion index).
            flat_v = values.reshape(-1)
            occupied = flat_v != 0
            order = np.argsort(flat_v[occupied], kind="stable")
            digests = keys.reshape(-1, 8)[occupied][order]
            self.n_entries = n_entries
            k2, v2 = _build_host_tables(digests, self.n_shards)
            # Stored values are original dict indices; remap the rebuilt
            # values (which index into `digests`) back onto them.
            orig = np.concatenate([[0], np.sort(flat_v[occupied])]).astype(np.int32)
            self._put_tables(k2, orig[v2.reshape(-1)].reshape(v2.shape))
            self._n_unique = int(occupied.sum())
        else:
            self.n_entries = n_entries
            self._put_tables(keys, values, max_depth=loaded_depth)
            self._n_unique = n_unique
        self.epoch = epoch
        self.rebuild_epoch = rebuild_epoch
        if tail is not None and len(tail):
            # Replay the appended entries with their original values
            # (probe-identical to the in-memory incremental inserts).
            rebuilt = self._insert_entries(
                np.ascontiguousarray(tail["d"]), tail["v"].astype(np.int64)
            )
            if not rebuilt:
                self._journal = [
                    (epoch, np.ascontiguousarray(tail["d"]), tail["v"].astype(np.int64))
                ]
        self.n_entries = n_entries
        return self

    # -- probing ------------------------------------------------------------

    def lookup_u32(self, queries_u32: np.ndarray) -> np.ndarray:
        """Probe a batch: u32[M,8] digests -> int64[M] dict indices (-1 = miss)."""
        queries_u32 = np.asarray(queries_u32, dtype=np.uint32).reshape(-1, 8)
        m = len(queries_u32)
        if m == 0:
            return np.zeros(0, dtype=np.int64)
        if self.n_entries == 0:
            return np.full(m, -1, dtype=np.int64)
        # One snapshot read: a concurrent insert/rebuild publishes tables +
        # capacity + depth together, so this probe is internally consistent.
        keys, values, cap, depth = self._tables
        if self._use_host_probe():
            from nydus_snapshotter_tpu.ops import native_cdc

            return native_cdc.dict_probe_native(
                queries_u32, keys.reshape(-1, 8), values.reshape(-1),
                self.n_shards, cap, depth,
            )
        if self.probe_backend == "pallas":
            return self._lookup_pallas(queries_u32)
        # Route unique queries only: duplicates would concentrate buckets
        # (and waste probe work); uniqueness restores the uniform digest
        # distribution the bucket capacity is sized for.
        void = np.ascontiguousarray(queries_u32).view(np.dtype((np.void, 32)))[:, 0]
        _, first, inverse = np.unique(void, return_index=True, return_inverse=True)
        uniq_ans = self._lookup_unique(queries_u32[first])
        return uniq_ans[inverse]

    def _lookup_pallas(self, queries_u32: np.ndarray) -> np.ndarray:
        """Single-host DMA-pipelined device probe (ops/probe_pallas): the
        TPU-native replacement for the XLA gather —
        the table stays in HBM, each query's chain window is DMA'd into
        VMEM with pipelined copies. Queries are partitioned by owning
        shard host-side; each shard's table is probed in one kernel
        launch."""
        from nydus_snapshotter_tpu.ops import probe_pallas

        m = len(queries_u32)
        host_keys, host_values, _cap, depth = self._tables
        shard_of = queries_u32[:, 0] % np.uint32(self.n_shards)
        out = np.zeros(m, dtype=np.int64)
        for s in range(self.n_shards):
            idx = np.nonzero(shard_of == s)[0]
            if not len(idx):
                continue
            ans = probe_pallas.probe(
                host_keys[s],
                host_values[s],
                queries_u32[idx],
                depth,
                interpret=self.pallas_interpret,
            )
            out[idx] = ans.astype(np.int64)
        return out - 1

    def _lookup_unique(self, queries_u32: np.ndarray) -> np.ndarray:
        m = len(queries_u32)
        pad = (-m) % self.n_shards
        if pad:
            queries_u32 = np.concatenate(
                [queries_u32, np.zeros((pad, 8), dtype=np.uint32)]
            )
        q = jax.device_put(
            queries_u32, NamedSharding(self.mesh, PartitionSpec(mesh_lib.AXIS_DATA))
        )
        dkeys, dvalues = self._device_tables()
        ans, overflowed = _probe_routed(
            dkeys, dvalues, q, self.n_shards, self.mesh, self.max_depth
        )
        if bool(np.any(np.asarray(jax.device_get(overflowed)))):
            ans = _probe_sharded(
                dkeys, dvalues, q, self.n_shards, self.mesh, self.max_depth
            )
        ans = np.asarray(jax.device_get(ans))[:m]
        return ans.astype(np.int64) - 1

    def lookup_digests(self, digests: list[bytes]) -> np.ndarray:
        """Probe raw 32-byte digests."""
        if not digests:
            return np.zeros(0, dtype=np.int64)
        arr = np.frombuffer(b"".join(digests), dtype="<u4").reshape(len(digests), 8)
        return self.lookup_u32(arr)
