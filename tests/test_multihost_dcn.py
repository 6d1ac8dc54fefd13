"""Real two-process jax.distributed (DCN) smoke (VERDICT r3 next #7).

parallel/multihost.runtime() had only ever run in its degraded
single-process mode; this test stands up an ACTUAL coordinator with two
localhost CPU processes — the same jax.distributed membership path a
multi-host TPU fleet uses over DCN — partitions a batch of images across
them, converts each slice, and verifies the union equals a
single-process conversion bit-for-bit (blob ids are content digests, so
equality proves identical blobs).

Reference correspondence: distribution stays behind the registry/storage
boundary (SURVEY §2.3) — hosts exchange membership only, never
conversion state.
"""

import io
import json
import os
import socket
import subprocess
import sys
import tarfile

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import json, os, sys
sys.path.insert(0, os.environ["NTPU_REPO"])
import jax
jax.config.update("jax_platforms", "cpu")  # virtual CPU devices only

from nydus_snapshotter_tpu.parallel import multihost

rt = multihost.runtime(
    coordinator=os.environ["COORD"],
    process_id=int(os.environ["PID_IDX"]),
    num_processes=2,
)
assert rt.count == 2, f"expected 2 joined processes, got {rt.count}"
assert rt.index == int(os.environ["PID_IDX"])

# Deterministic partition of the shared image list.
import numpy as np
from nydus_snapshotter_tpu.converter.convert import pack_layer
from nydus_snapshotter_tpu.converter.types import PackOption

n_images = int(os.environ["N_IMAGES"])
mine = rt.shard(list(range(n_images)))

out = {}
for i in mine:
    rng = np.random.default_rng(1000 + i)
    import io, tarfile
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w", format=tarfile.GNU_FORMAT) as tf:
        for f in range(4):
            size = int(rng.integers(1000, 120_000))
            ti = tarfile.TarInfo(f"img{i}/f{f}")
            ti.size = size
            tf.addfile(ti, io.BytesIO(rng.integers(0, 256, size, dtype=np.uint8).tobytes()))
    blob, res = pack_layer(buf.getvalue(), PackOption(chunk_size=0x10000))
    out[i] = res.blob_id

print("RESULT " + json.dumps({"index": rt.index, "count": rt.count, "blobs": out}))
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_dcn_coordinator():
    n_images = 6
    port = _free_port()
    env_base = {
        **os.environ,
        "NTPU_REPO": REPO,
        "COORD": f"127.0.0.1:{port}",
        "N_IMAGES": str(n_images),
        # the child pins the CPU platform via jax.config before any
        # backend init
    }
    procs = []
    for idx in range(2):
        env = dict(env_base)
        env["PID_IDX"] = str(idx)
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", _CHILD],
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
                env=env,
                cwd=REPO,
            )
        )
    results = {}
    for p in procs:
        out, err = p.communicate(timeout=240)
        assert p.returncode == 0, (out[-500:], err[-2000:])
        line = next(l for l in out.splitlines() if l.startswith("RESULT "))
        r = json.loads(line[len("RESULT ") :])
        assert r["count"] == 2  # real membership, not the degraded mode
        results[r["index"]] = {int(k): v for k, v in r["blobs"].items()}

    assert set(results) == {0, 1}
    # Disjoint, complete strided partition.
    assert set(results[0]) == {0, 2, 4}
    assert set(results[1]) == {1, 3, 5}

    # Single-process conversion of the same images gives identical blobs.
    from nydus_snapshotter_tpu.converter.convert import pack_layer
    from nydus_snapshotter_tpu.converter.types import PackOption

    merged = {**results[0], **results[1]}
    for i in range(n_images):
        rng = np.random.default_rng(1000 + i)
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w", format=tarfile.GNU_FORMAT) as tf:
            for f in range(4):
                size = int(rng.integers(1000, 120_000))
                ti = tarfile.TarInfo(f"img{i}/f{f}")
                ti.size = size
                tf.addfile(
                    ti,
                    io.BytesIO(rng.integers(0, 256, size, dtype=np.uint8).tobytes()),
                )
        _blob, res = pack_layer(buf.getvalue(), PackOption(chunk_size=0x10000))
        assert merged[i] == res.blob_id, f"image {i} diverged across the fleet"


def test_genuine_join_failure_never_degrades():
    """An unreachable coordinator must never degrade to a (0,1) singleton
    (which would silently re-convert the whole image list). jax surfaces
    the failure either as a Python RuntimeError or — current behavior —
    by terminating the process with a fatal DEADLINE_EXCEEDED; both are
    acceptable, a DEGRADED success is not.

    Deflaked (ISSUE 15): PR 14 recorded this failing only under
    concurrent core saturation — the child pays a full fresh-interpreter
    jax import BEFORE its own 10s join deadline even starts, and the old
    flat 120s subprocess timeout charged the import against the join.
    The timing assumption is fixed the same way the PR-8/PR-12 isolated
    re-execs budget their children: a short JOIN deadline (5s — the
    thing under test), a LONG outer wall (420s — covers a starved
    import), and pgroup kill + honest failure instead of a raw
    TimeoutExpired when even that is blown."""
    import signal

    child = (
        "import os, sys; sys.path.insert(0, os.environ['NTPU_REPO']);\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "from nydus_snapshotter_tpu.parallel import multihost\n"
        "try:\n"
        "    multihost.runtime(coordinator='127.0.0.1:1', process_id=1, num_processes=2, init_timeout_s=5)\n"
        "except Exception as e:\n"
        "    print('RAISED', type(e).__name__); raise SystemExit(17)\n"
        "print('DEGRADED'); raise SystemExit(0)\n"
    )
    proc = subprocess.Popen(
        [sys.executable, "-c", child],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        env={**os.environ, "NTPU_REPO": REPO},
        cwd=REPO,
        start_new_session=True,  # a wedge is killed as a whole pgroup
    )
    try:
        stdout, stderr = proc.communicate(timeout=420)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        stdout, stderr = proc.communicate()
        pytest.fail(
            "join-failure child wedged past the 420s wall (pgroup killed):\n"
            + (stderr or "")[-800:]
        )
    assert "DEGRADED" not in stdout, stdout
    assert proc.returncode != 0
    assert "RAISED" in stdout or "DEADLINE_EXCEEDED" in stderr, (
        stdout,
        stderr[-800:],
    )


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))


_DICT_CHILD = r"""
import io, json, os, sys, tarfile
sys.path.insert(0, os.environ["NTPU_REPO"])
import jax
jax.config.update("jax_platforms", "cpu")  # virtual CPU devices only

import numpy as np
from nydus_snapshotter_tpu.parallel import multihost
from nydus_snapshotter_tpu.converter.convert import Merge, pack_layer
from nydus_snapshotter_tpu.converter.types import MergeOption, PackOption
from nydus_snapshotter_tpu.models.bootstrap import Bootstrap, ChunkDict

rt = multihost.runtime(
    coordinator=os.environ["COORD"],
    process_id=int(os.environ["PID_IDX"]),
    num_processes=2,
)
share = os.environ["SHARE_DIR"]  # the storage boundary (registry stand-in)
opt = PackOption(chunk_size=0x10000)


def _result(payload):
    # Per-worker result FILE, written atomically: stdout of a multihost
    # child interleaves worker prints with jax/absl logging, and scraping
    # it flaked (VERDICT r5 #7). The parent reads RESULT_PATH instead.
    path = os.environ["RESULT_PATH"]
    with open(path + ".tmp", "w") as f:
        json.dump(payload, f)
    os.rename(path + ".tmp", path)


def image_tar(seed, pool):
    rng = np.random.default_rng(seed)
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w", format=tarfile.GNU_FORMAT) as tf:
        for f in range(5):
            data = pool[rng.integers(0, len(pool))]
            ti = tarfile.TarInfo(f"app/f{seed}-{f}")
            ti.size = len(data)
            tf.addfile(ti, io.BytesIO(data))
    return buf.getvalue()


prng = np.random.default_rng(777)  # SHARED content pool: cross-host overlap
pool = [prng.integers(0, 256, 60_000, dtype=np.uint8).tobytes() for _ in range(8)]

if rt.index == 0:
    # Host 0: convert the base image, publish its merged bootstrap as the
    # fleet's chunk-dict artifact (the reference ships dict bootstraps
    # through the registry the same way).
    blob, res = pack_layer(image_tar(1, pool), opt)
    merged = Merge([blob], MergeOption(with_tar=False))
    with open(os.path.join(share, "dict.boot.tmp"), "wb") as f:
        f.write(merged.bootstrap)
    os.rename(os.path.join(share, "dict.boot.tmp"), os.path.join(share, "dict.boot"))
    rt.barrier("dict-published")
    _result({"index": 0, "dict_chunks": len(
        ChunkDict(Bootstrap.from_bytes(merged.bootstrap)))})
else:
    rt.barrier("dict-published")  # wait for host 0's artifact
    cdict = ChunkDict.from_path(os.path.join(share, "dict.boot"))
    blob, res = pack_layer(image_tar(2, pool), opt, chunk_dict=cdict)
    from nydus_snapshotter_tpu.converter.convert import bootstrap_from_layer_blob
    bs = bootstrap_from_layer_blob(blob)
    foreign = sum(
        c.uncompressed_size
        for c in bs.chunks
        if bs.blobs[c.blob_index].blob_id != res.blob_id
    )
    total = sum(c.uncompressed_size for c in bs.chunks)
    _result({
        "index": 1, "dedup_bytes": foreign, "total_bytes": total,
        "referenced": sorted({bs.blobs[c.blob_index].blob_id for c in bs.chunks}),
        "own": res.blob_id,
    })
"""


def test_cross_host_chunk_dict_over_storage_boundary(tmp_path):
    """Two-host dict handoff: host 0 converts and PUBLISHES its merged
    bootstrap as the dict artifact; a DCN barrier gates host 1, which
    loads it from the shared store and converts a content-overlapping
    image against it — cross-host dedup must produce real foreign-blob
    references. DCN carries only membership + the barrier; conversion
    state crosses hosts exclusively through the storage boundary,
    exactly the reference's distribution model (SURVEY §2.3)."""
    port = _free_port()
    share = str(tmp_path / "registry")
    os.makedirs(share)
    env_base = {
        **os.environ,
        "NTPU_REPO": REPO,
        "COORD": f"127.0.0.1:{port}",
        "SHARE_DIR": share,
    }
    procs = []
    result_paths = []
    for idx in range(2):
        env = dict(env_base)
        env["PID_IDX"] = str(idx)
        # Per-worker result file, not stdout scraping: multihost children
        # interleave prints with jax/absl logging on the same fd, and the
        # RESULT line intermittently arrived torn (VERDICT r5 #7).
        result_path = str(tmp_path / f"result{idx}.json")
        env["RESULT_PATH"] = result_path
        result_paths.append(result_path)
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", _DICT_CHILD],
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
                env=env,
                cwd=REPO,
            )
        )
    results = {}
    for p, result_path in zip(procs, result_paths):
        out, err = p.communicate(timeout=240)
        assert p.returncode == 0, (out[-500:], err[-2000:])
        with open(result_path) as f:
            r = json.load(f)
        results[r["index"]] = r
    assert results[0]["dict_chunks"] > 0
    r1 = results[1]
    assert r1["dedup_bytes"] > 0, "no cross-host dedup hits"
    assert r1["dedup_bytes"] <= r1["total_bytes"]
    # host 1's bootstrap must reference BOTH its own blob and host 0's
    assert r1["own"] in r1["referenced"]
    assert len(r1["referenced"]) == 2
