"""One run of one cell of BENCHMARK.json, on the machine it is started on.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything worth knowing is printed on earlier lines of standard output, one
JSON object a line; the LAST line is the result. Exits non-zero, with no
result line, when JAX finds no TPU or fewer chips than the cell asks for.
Finds the cell, its configuration, its traffic kind and each metric's reader
by the names in BENCHMARK.json (see benchmark/README.md).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
from itertools import groupby  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def work_root() -> str:
    """$TMPDIR, or a git-ignored directory of the checkout: never a fixed /tmp path."""
    root = os.environ.get("TMPDIR") or os.path.join(ROOT, ".bench_work")
    os.makedirs(root, exist_ok=True)
    return root


def percentile_nearest_rank(values: list[float], q: float) -> float:
    ordered = sorted(values)
    return ordered[max(0, math.ceil(len(ordered) * q / 100) - 1)]


def run_verb(program, verb: str, layer: int, nbytes: int, argv: list[str], k: int, caller: int = 0) -> dict:
    import jax

    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(f"pack:{layer}" if verb == "pack" else verb):
        try:
            result, ok = program.cli(argv), True
        except Exception as e:  # noqa: BLE001 - a failed verb is counted, not fatal
            result, ok = {"error": f"{type(e).__name__}: {e}"}, False
    t1 = time.perf_counter()
    return {"verb": verb, "layer": layer, "iter": k, "caller": caller, "bytes": nbytes, "t0": t0, "t1": t1,
            "ok": ok, "result": result}


def send(program, batch: list, k: int, caller: int) -> list:
    """The verbs of ``batch`` at once, each from a thread of its own (one
    process owns the chip: concurrent callers of a device converter ARE
    threads of it); a batch of one on the calling thread -> their records,
    in the batch's order."""
    if len(batch) == 1:
        return [run_verb(program, *batch[0], k, caller)]
    with ThreadPoolExecutor(len(batch)) as pool:
        return list(pool.map(lambda verb: run_verb(program, *verb, k, caller), batch))


def convert(program, loop, out_dir: str, k: int, records: list, deadline: float | None = None,
            at_once: bool = False, caller: int = 0) -> bool:
    """One whole convert by one caller; False when the deadline cut it short.
    With ``at_once`` the image's ``pack`` verbs are sent together and ``merge``
    when all have answered, failed or not (containerd's converter: one
    goroutine a layer, then the merge hook); without, verb after verb.
    What the deadline stops is what the caller sends next: verb after verb
    that is a verb, with ``at_once`` an image, whose ``merge`` is part of what
    was sent. So such a window holds whole converts only: cut before a
    ``merge`` it would count the packs and not the merge's seconds, and
    ``convert_mib_per_s`` stepped 5% on whether the seventh merge ended at
    39.9 or 40.1 s (PERF.md §6, PR 35)."""
    verbs = list(loop.verbs(out_dir))
    batches = [list(g) for _verb, g in groupby(verbs, key=lambda v: v[0])] if at_once else [[v] for v in verbs]
    for i, batch in enumerate(batches):
        if deadline is not None and not (at_once and i) and time.perf_counter() >= deadline:
            return False
        records += send(program, batch, k, caller)
    return True


def profiler_session():
    """A running profiler session: the one behind ``jax.profiler.start_trace``,
    used directly because ``jax.profiler.stop_trace`` also writes every event
    as gzipped JSON, 1-3 minutes of host time for one pack's ~6 million op
    events. Ends with ``stop_and_get_profile_data()`` or ``stop()`` (bytes)."""
    import jax

    try:  # private: a JAX that moves it must stop the traced run loudly, not leave it without a trace
        from jax._src.lib import _profiler
    except ImportError as e:
        raise RuntimeError(f"benchmark: jax {jax.__version__} has no jax._src.lib._profiler.ProfilerSession "
                           f"(written against jax 0.9.0); give profiler_session() its new home") from e

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the host spans are the harness's own annotations
    options.enable_hlo_proto = False  # nothing here reads the programs' HLO
    return _profiler.ProfilerSession(options)


def traced_verbs(program, batch: list, k: int) -> tuple[list, object]:
    """The verbs of ``batch``, sent at once, under ONE profiler session ->
    (their records, the trace as ProfileData)."""
    session = profiler_session()
    try:
        records = send(program, batch, k, 0)
    finally:
        t0 = time.perf_counter()
        data = session.stop_and_get_profile_data()
    stop_s = time.perf_counter() - t0
    return [{**r, "traced": True, "stop_s": stop_s} for r in records], data


def caller_loop(program, loop, caller: int, work: str, deadline: float, at_once: bool, draws) -> dict:
    """One caller's closed loop on directories of its own, until the deadline,
    ending with the verbs (with ``at_once``: the image) in flight. Keeps the artifacts of one whole convert
    drawn from the seed (reservoir of one, by the caller's own ``draws``) and of the last."""
    tag = f".{caller}" if caller else ""
    out_dir, keep_dir = os.path.join(work, "out" + tag), os.path.join(work, "kept" + tag)
    os.makedirs(out_dir)
    records, k = [], 0
    while convert(program, loop, out_dir, k, records, deadline, at_once, caller):
        if draws[k % len(draws)] < 1.0 / (k + 1):
            shutil.rmtree(keep_dir, ignore_errors=True)
            os.rename(out_dir, keep_dir)
            os.makedirs(out_dir)
        k += 1
    return {"records": records, "converts": k, "out": out_dir, "kept": keep_dir}


def window(program, loop, seconds: float, work: str, trace: bool, log) -> tuple[list, list, object]:
    """The mix's ``clients`` callers, each in the closed loop, for ``seconds``
    -> (records, kept directories, the trace or None). ``layers_at_once``: a
    caller sends its image's packs together. With ``trace`` the loop's next
    image is then sent the way one caller sends it, under the profiler: its
    first pack alone, or with ``layers_at_once`` its packs together. No more,
    because a pack alone fills the device's trace buffer with millions of op
    events, and after the window, because ending the session takes the host
    15-90 s that no other pack of the window should wait for."""
    import numpy as np

    clients, at_once = int(loop.cell["clients"]), bool(loop.cell.get("layers_at_once", False))
    draws = np.random.default_rng([int(loop.seed), 0xE7]).random(1 << 12)
    deadline = time.perf_counter() + seconds
    each = lambda c: caller_loop(program, loop, c, work, deadline, at_once, draws[c::clients])  # noqa: E731
    if clients == 1:
        # on this thread, as every mix before `fanout` was sent: from a pool's thread node21-64k.dict and
        # smallfiles-64k.fresh read 12-17% under the parent's loop on the chip machine (PERF.md §6, PR 35)
        callers = [each(0)]
    else:
        with ThreadPoolExecutor(clients) as pool:
            callers = list(pool.map(each, range(clients)))
    records = sorted((r for c in callers for r in c["records"]), key=lambda r: r["t0"])
    profile = None
    if trace:
        packs = [v for v in loop.verbs(callers[0]["out"]) if v[0] == "pack"]
        traced, profile = traced_verbs(program, packs if at_once else packs[:1], callers[0]["converts"])
        records += traced
    log("window", callers=clients, layers_at_once=at_once, converts=[c["converts"] for c in callers],
        verbs=len(records), traced=trace)
    start = deadline - seconds  # every verb as (caller, verb, layer, convert, sent, answered): seconds into the window
    log("timeline", verbs=[[r["caller"], r["verb"], r["layer"], r["iter"], round(r["t0"] - start, 3),
                            round(r["t1"] - start, 3)] for r in records])
    kept = [d for c in callers for d in (c["kept"], c["out"]) if os.path.isdir(d) and os.listdir(d)]
    return records, kept, profile


def end_to_end(records: list, setup_s: float) -> dict:
    packs = [r for r in records if r["verb"] == "pack" and r["ok"]]
    wall = max(r["t1"] for r in records) - min(r["t0"] for r in records)
    per_gib = [(r["t1"] - r["t0"]) / (r["bytes"] / 2**30) for r in packs]
    return {
        "convert_mib_per_s": sum(r["bytes"] for r in packs) / 2**20 / wall,
        "pack_p95_s_per_gib": percentile_nearest_rank(per_gib, 95),
        "setup_s": setup_s,
    }


def main(argv=None, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # Our stdout is for our lines alone: the real fd is kept aside, and fd 1 /
    # sys.stdout (make, libtpu, stray prints) go to stderr.
    sys.stdout.flush()
    out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    stdout_was, sys.stdout = sys.stdout, sys.stderr

    def log(phase: str, **facts) -> None:
        print(json.dumps({"phase": phase, **facts}, default=str), file=out, flush=True)

    try:
        return _run(args, require_tpu, log, out)
    finally:
        sys.stdout = stdout_was


def find_cell(workload: str) -> tuple[dict, dict, dict, dict]:
    """-> (BENCHMARK.json, the cell's entry, its traffic mix, its configuration), by name."""
    bench = load(ROOT, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"benchmark: no workload {workload!r} in BENCHMARK.json")
    return (bench, entry, load(HERE, "traffic", "mixes", f"{entry['traffic']}.json"),
            load(HERE, "configs", f"{entry['config']}.json"))


def metric_file(name: str) -> str:
    """``<name>.json`` under metrics/, or for a quantity split by cells
    (``lane_host_s_per_gib.fanout``) that has no file of its own, its base
    name's: the same reader with the same parameters."""
    if os.path.exists(os.path.join(HERE, "metrics", f"{name}.json")) or "." not in name:
        return f"{name}.json"
    return f"{name.rsplit('.', 1)[0]}.json"


def reported(bench: dict, workload: str) -> tuple[list, list]:
    """-> (the end-to-end metrics, the per-layer metrics) that ``workload``
    reports: those whose ``workloads`` lists it or that have no such list, and
    of the per-layer ones only those that move an end-to-end metric it reports."""
    ours = lambda m: workload in m.get("workloads", [workload])  # noqa: E731
    e2e = [m for m in bench["end_to_end"] if ours(m)]
    moved = {m["name"] for m in e2e}
    return e2e, [m for m in bench["per_layer"] if ours(m) and m["moves"] in moved]


def _run(args, require_tpu: bool, log, out) -> int:
    bench, entry, cell, config = find_cell(args.workload)
    e2e_metrics, layer_metrics = reported(bench, args.workload)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        print(f"benchmark: needs a TPU, JAX found {dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 3
    if len(devices) < entry["chips"]:
        print(f"benchmark: the cell asks for {entry['chips']} chip(s), JAX found {len(devices)}", file=sys.stderr)
        return 3
    peaks = load(HERE, "peaks.json")["device_kinds"].get(dev.device_kind)
    if peaks is None and require_tpu:
        print(f"benchmark: no peaks for device kind {dev.device_kind!r} in peaks.json", file=sys.stderr)
        return 3

    from benchmark import program, verify

    cache = program.prepare()
    misses = program.count_cache_misses()
    log("start", workload=args.workload, seed=args.seed, seconds=args.seconds, trace=args.trace,
        platform=dev.platform, kind=dev.device_kind, devices=len(devices), jax=jax.__version__,
        compile_cache=cache, compile_cache_entries=len(os.listdir(cache)) if os.path.isdir(cache) else 0)

    work = tempfile.mkdtemp(prefix="ntpu_bench.", dir=work_root())
    try:
        kind = importlib.import_module(f"benchmark.traffic.{cell['kind']}")
        loop = kind.build(cell, config, args.seed, work, log)
        base = program.counters()
        loop.generate()
        warm = []
        warm_dir = os.path.join(work, "warm")
        os.makedirs(warm_dir)
        convert(program, loop, warm_dir, -1, warm)  # every program compiled or loaded here
        shutil.rmtree(warm_dir)
        setup = {"lane_programs": program.lane_programs(), "compile_cache_misses": misses[0]}
        log("warmup", verbs=[{k: r[k] for k in ("verb", "layer", "ok")} | {"wall_s": r["t1"] - r["t0"]} for r in warm],
            **setup)
        if not all(r["ok"] for r in warm):
            print(f"benchmark: the warm-up convert failed: {[r['result'] for r in warm if not r['ok']]}",
                  file=sys.stderr)
            return 1
        setup_s = time.perf_counter() - T_START

        at_window = program.counters()
        records, kept, profile = window(program, loop, args.seconds, work, bool(args.trace), log)
        programs_after = program.lane_programs()
        now = program.counters()
        counters = {k: now[k] - base[k] for k in now}  # since before set-up: what the comparison reads
        in_window = {k: now[k] - at_window[k] for k in now}  # all callers' seconds added up: what the readers read
        stats = dev.memory_stats() or {}
        device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
                  "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}

        metrics, breakdown = {}, None
        if args.trace:
            from benchmark import trace_reduce

            t0 = time.perf_counter()
            trace = trace_reduce.reduce(profile)
            del profile
            log("trace", reduce_s=time.perf_counter() - t0, stop_s=records[-1]["stop_s"],
                **{k: trace.get(k) for k in ("structure", "spans", "busy_s", "window_s")})
            ctx = {"records": records, "counters": in_window, "trace": trace, "setup": setup, "peaks": peaks,
                   "loop": loop}
            for m in layer_metrics:
                spec = load(HERE, "metrics", metric_file(m["name"]))
                reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
                value = reader.read(ctx, **spec.get("params", {}))
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            if "busy_s" in trace:
                device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
                breakdown = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
        else:
            values = end_to_end(records, setup_s)
            for m in e2e_metrics:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        log("measured", programs_compiled_in_window=programs_after - setup["lane_programs"],
            counters=counters, window_counters=in_window, **end_to_end(records, setup_s))

        # the comparison: after the window, outside setup_s and the timed wall
        t0 = time.perf_counter()
        ref_dir = os.path.join(work, "ref")
        ref_lines = verify.run_reference(loop, ref_dir)
        # the host-only convert of the same tars is also the host's witness: the
        # machine's host has a fast and a slow state, and this number follows it
        log("reference", backend="hybrid", host_witness_s=time.perf_counter() - t0)
        checks = verify.compare(loop, records, kept, ref_dir, ref_lines, counters, log, setup=warm)
        checks.append(verify.check("programs_compiled_in_window", programs_after - setup["lane_programs"], 0))
        log("compared", wall_s=time.perf_counter() - t0)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    said = {c["name"]: {"value": c["value"], "limit": c["limit"], "rule": c["rule"]} for c in checks}
    result = {"correct": all(c["ok"] for c in checks), "attempted": len(records),
              "failed": sum(not r["ok"] for r in records), "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result["checks"] = said
    for c in checks:
        print(f"check {c['name']}: {c['value']} (limit {c['rule']} {c['limit']})"
              f"{'' if c['ok'] else '  <-- NOT MET'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
