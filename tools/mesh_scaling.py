"""Gated weak-scaling curve for the extent-packed sharded convert step.

Measures __graft_entry__.sharded_convert_step — gear bitmaps, cut
resolution, extent planning (ops/mesh_pack), gather+digest via shard_map,
bootstrap emit — over 1/2/4/8 virtual devices, corpus growing with the
device count (weak scaling: constant bytes per device). Each mesh size
runs in a fresh subprocess so XLA_FLAGS can set the virtual device count
before backend init. Both operand layouts run PAIRED in the same child:

- ``extent``: per-device packed slabs (shard + read-span halo), nothing
  device-count-replicated — the production layout;
- ``replicated``: the identical bucket partition with the whole corpus
  broadcast to every device — what MESH_SCALING_r05 measured (0.214
  "efficiency" at 8 devices, dominated by n× corpus replication).

Gates (abort-on-fail, the noisy-box discipline: paired best-rep ratios
plus exact/analytic bounds that wall noise cannot touch):

1. identity — cuts/digests/bootstrap byte-identical across extent,
   replicated and the single-device host oracle at every point;
2. no-replicated-operand — MEASURED per-device addressable corpus bytes
   of the extent arm ≤ corpus/devices + halo at every point, while the
   replicated arm is recorded holding the full corpus per device;
3. analytic bytes-transferred bound — extent total device bytes ≤
   corpus + n·halo vs the replicated arm's n·corpus (ratio recorded);
4. weak-scaling efficiency ≥ --min-efficiency (default 0.6) at the
   largest mesh, eff(n) = wall_1 · ceil-ideal / wall_n where the ideal
   accounts for devices time-sharing host cores (on c cores the best
   possible wall for n× the work on n virtual devices is wall_1·n/c for
   n ≥ c; on a real ≥n-core/chip host the formula reduces to the
   textbook wall_1/wall_n). The r05 definition (throughput /
   devices·base-throughput) is kept as ``throughput_ratio`` for series
   continuity — on a time-shared core it is bounded by ~1/n and says
   nothing about partitioning;
5. paired arm ratio — extent best-rep wall ≤ replicated best-rep wall ×
   (1 + --arm-tolerance), same process, alternating reps.

Usage: python tools/mesh_scaling.py [--out MESH_SCALING_r06.json]
       [--per-dev-kib 2048] [--reps 3] [--min-efficiency 0.6] [--no-gate]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import json, os, sys, time
import numpy as np
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
import __graft_entry__ as g
from nydus_snapshotter_tpu.parallel import mesh as mesh_lib
from nydus_snapshotter_tpu.ops.chunker import ChunkDigestEngine

n = {n}
reps = {reps}
mesh = mesh_lib.make_mesh(n)
rng = np.random.default_rng(11)
files = [
    rng.integers(0, 256, {per_dev_kib} * 1024 // 4, dtype=np.uint8).tobytes()
    for _ in range(4 * n)
]
total = sum(len(f) for f in files)

# warm-up compiles every shape for BOTH arms, and captures the plan
# geometry + measured per-device addressable bytes for the gates
rep_ext, rep_repl = dict(), dict()
cuts_e, digs_e, boot_e = g.sharded_convert_step(
    files, 0x1000, n, mesh, pack="extent", report=rep_ext
)
cuts_r, digs_r, boot_r = g.sharded_convert_step(
    files, 0x1000, n, mesh, pack="replicated", report=rep_repl
)

# identity: extent == replicated == single-device host oracle
oracle = ChunkDigestEngine(chunk_size=0x1000, backend="numpy", digest_backend="numpy")
truth = oracle.process_many(files)
cuts_t = [np.asarray([m.offset + m.size for m in ms], np.int64) for ms in truth]
digs_t = [[m.digest for m in ms] for ms in truth]
identity_ok = (
    boot_e == boot_r
    and digs_e == digs_t
    and all((np.asarray(a) == b).all() for a, b in zip(cuts_e, cuts_t))
)

# paired reps: alternate arms inside one process so drift hits both
best = dict(extent=None, replicated=None)
for _ in range(reps):
    for arm in ("extent", "replicated"):
        t0 = time.time()
        g.sharded_convert_step(files, 0x1000, n, mesh, pack=arm)
        dt = time.time() - t0
        if best[arm] is None or dt < best[arm]:
            best[arm] = dt

print("RESULT " + json.dumps(dict(
    devices=n,
    total=total,
    chunks=sum(len(d) for d in digs_e),
    wall_extent_s=best["extent"],
    wall_replicated_s=best["replicated"],
    identity_ok=bool(identity_ok),
    extent=rep_ext,
    replicated=rep_repl,
)))
"""


def _run(n: int, per_dev_kib: int, reps: int) -> dict:
    env = dict(os.environ)
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "", env.get("XLA_FLAGS", "")
    )
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"  # virtual devices: the child needs no chip
    out = subprocess.run(
        [
            sys.executable,
            "-c",
            _CHILD.format(repo=REPO, n=n, per_dev_kib=per_dev_kib, reps=reps),
        ],
        capture_output=True,
        text=True,
        env=env,
        timeout=1800,
        cwd=REPO,
    )
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-1200:])
    for line in reversed(out.stdout.strip().splitlines()):
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"no RESULT line from {n}-device child")


def _gate(ok: bool, label: str, detail: str, failures: list[str]) -> None:
    print(f"[{'PASS' if ok else 'FAIL'}] {label}: {detail}")
    if not ok:
        failures.append(f"{label}: {detail}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "MESH_SCALING_r06.json"))
    ap.add_argument("--per-dev-kib", type=int, default=2048)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--devices", default="1,2,4,8")
    ap.add_argument("--min-efficiency", type=float, default=0.6)
    ap.add_argument(
        "--arm-tolerance",
        type=float,
        default=0.25,
        help="extent wall may exceed replicated wall by at most this "
        "fraction (best-rep paired; ~2x rep-to-rep wall noise on the "
        "1-core box is why this is not a raw speedup gate)",
    )
    ap.add_argument("--no-gate", action="store_true")
    args = ap.parse_args()

    ns = [int(x) for x in args.devices.split(",") if x]
    cores = os.cpu_count() or 1
    raw = [_run(n, args.per_dev_kib, args.reps) for n in ns]

    base = raw[0]
    points = []
    for r in raw:
        n = r["devices"]
        ideal_wall = base["wall_extent_s"] * max(1.0, n / cores)
        points.append(
            {
                "devices": n,
                "corpus_mib": round(r["total"] / (1 << 20), 2),
                "chunks": r["chunks"],
                "wall_s": round(r["wall_extent_s"], 3),
                "wall_replicated_s": round(r["wall_replicated_s"], 3),
                "mibps": round(r["total"] / r["wall_extent_s"] / (1 << 20), 2),
                "identity_ok": r["identity_ok"],
                "weak_scaling_efficiency": round(
                    ideal_wall / r["wall_extent_s"], 3
                ),
                "throughput_ratio": round(
                    (r["total"] / r["wall_extent_s"])
                    / (n * base["total"] / base["wall_extent_s"]),
                    3,
                ),
                "arm_wall_ratio": round(
                    r["wall_extent_s"] / r["wall_replicated_s"], 3
                ),
                "max_device_bytes": r["extent"]["max_device_bytes"],
                "bound_bytes": r["extent"]["bound_bytes"],
                "replicated_device_bytes": r["replicated"]["max_device_bytes"],
                "device_bytes_ratio": round(
                    r["extent"]["max_device_bytes"]
                    / max(1, r["replicated"]["max_device_bytes"]),
                    4,
                ),
            }
        )

    failures: list[str] = []
    for p in points:
        _gate(
            p["identity_ok"],
            f"identity@{p['devices']}dev",
            "extent == replicated == host oracle",
            failures,
        )
        _gate(
            p["max_device_bytes"] <= p["bound_bytes"],
            f"no-replicated-operand@{p['devices']}dev",
            f"{p['max_device_bytes']} B/device <= corpus/devices + halo "
            f"= {p['bound_bytes']} B (replicated arm held "
            f"{p['replicated_device_bytes']} B/device)",
            failures,
        )
        # analytic bytes-transferred bound: total packed bytes across the
        # mesh vs the replicated arm's n x corpus — exact, noise-free
        n = p["devices"]
        packed_total = p["max_device_bytes"] * n
        repl_total = p["replicated_device_bytes"] * n
        corpus = int(p["corpus_mib"] * (1 << 20))
        _gate(
            packed_total <= corpus + n * raw[0]["extent"]["halo_bytes"] + n * 8,
            f"bytes-bound@{n}dev",
            f"packed total {packed_total} B <= corpus + n*halo "
            f"(replicated total {repl_total} B, ratio "
            f"{packed_total / max(1, repl_total):.3f})",
            failures,
        )
        _gate(
            p["arm_wall_ratio"] <= 1.0 + args.arm_tolerance,
            f"paired-arm-wall@{n}dev",
            f"extent/replicated best-rep wall {p['arm_wall_ratio']} "
            f"<= {1.0 + args.arm_tolerance}",
            failures,
        )
    last = points[-1]
    _gate(
        last["weak_scaling_efficiency"] >= args.min_efficiency,
        f"weak-scaling-efficiency@{last['devices']}dev",
        f"{last['weak_scaling_efficiency']} >= {args.min_efficiency} "
        f"(time-share-normalized; ideal accounts {cores} host core(s))",
        failures,
    )

    rec = {
        "artifact": os.path.splitext(os.path.basename(args.out))[0],
        "step": "__graft_entry__.sharded_convert_step (full convert step, "
        "extent-packed per-device buffers)",
        "mode": "weak scaling: 4 files x per_dev_kib/4 per device; paired "
        "extent-vs-replicated reps in one child per mesh size",
        "host_cores": cores,
        "environment_note": (
            "virtual CPU mesh: devices time-share "
            f"{cores} host core(s). weak_scaling_efficiency therefore "
            "normalizes to the machine ideal wall_1*n/cores (on a real "
            ">=n-core/chip host the same formula is the textbook "
            "wall_1/wall_n); values > 1 mean per-run fixed overheads "
            "amortize with corpus size. throughput_ratio keeps the r05 "
            "definition for series continuity — it is bounded by ~1/n "
            "on a time-shared core and is NOT the gate."
        ),
        "gates": {
            "identity": "extent == replicated == host oracle, every point",
            "no_replicated_operand": "measured addressable bytes/device "
            "<= corpus/devices + halo, every point",
            "bytes_bound": "packed mesh total <= corpus + n*halo "
            "(replicated arm: n*corpus)",
            "min_efficiency_at_max_devices": args.min_efficiency,
            "arm_wall_tolerance": args.arm_tolerance,
        },
        "points": points,
        "weak_scaling_efficiency": {
            str(p["devices"]): p["weak_scaling_efficiency"] for p in points
        },
        "throughput_ratio_r05_definition": {
            str(p["devices"]): p["throughput_ratio"] for p in points
        },
    }
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec, indent=1))
    if failures and not args.no_gate:
        print(
            "MESH SCALING GATES FAILED:\n  " + "\n  ".join(failures),
            file=sys.stderr,
        )
        sys.exit(1)


if __name__ == "__main__":
    main()
