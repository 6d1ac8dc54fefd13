"""Banked-benchmark trajectory: render the repo's ``*_rNN.json``
artifacts as a cross-revision regression table.

Every profile run this repo gates on banks its report at the repo root
(``BENCH_r01.json``, ``MULTICORE_r05.json``, ``PROVENANCE_r01.json``,
...). Each family's revisions are a longitudinal record of the same
workload on the same class of box — this tool joins consecutive
revisions per family, flattens the numeric leaves, and prints the
paired deltas so a regression that slipped past one revision's gate is
still visible in the trend.

Direction is inferred per key: wall/latency/overhead-like keys are
lower-is-better, throughput/ratio-like keys higher-is-better; keys
with no clear direction are reported but never flagged. Numeric rep
lists collapse to their BEST value first (min for lower-is-better, max
for higher-is-better) so the comparison is paired-best-rep, matching
how the gates themselves score noisy walls. Deltas past ``--threshold``
percent in the bad direction are flagged ``REGRESSED``.

Non-gating by default: CI runs this as a report step (``|| true``), and
even bare it exits 0 unless ``--fail-on-regression`` is passed —
the per-profile gates, not the trend table, decide pass/fail.

Usage: python tools/bench_trend.py [--threshold 10] [--json]
                                   [--family BENCH] [--fail-on-regression]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_REV_RE = re.compile(r"^([A-Z][A-Z0-9_]*)_r(\d+)\.json$")

#: Substrings marking a key lower-is-better (walls, latencies, costs).
_LOWER = (
    "wall", "_s", "_ms", "_ns", "secs", "seconds", "latency", "overhead",
    "p50", "p95", "p99", "ns_per", "cost", "cold_bytes", "wasted",
    "dropped", "errors", "crashes", "untagged",
)
#: Substrings marking a key higher-is-better (throughput, accuracy).
_HIGHER = (
    "gibps", "mibps", "per_sec", "throughput", "ops", "accuracy",
    "efficiency", "dedup", "ratio_vs", "reduction", "hit", "value",
    "spans_per", "coverage",
)
#: Leaves that look numeric but are identifiers/config, never scored.
_SKIP = (
    "seed", "pid", "tid", "port", "rc", "n_devices", "version", "rev",
    "capacity", "chunk_size", "pods", "layers", "reps", "cores",
    "threads", "workers", "epoch", "budget", "stride", "window",
)


def direction(key: str) -> str:
    """'lower' | 'higher' | 'info' for a dotted leaf path."""
    leaf = key.rsplit(".", 1)[-1].lower()
    if any(s in leaf for s in _SKIP):
        return "info"
    if any(s in leaf for s in _HIGHER):
        return "higher"
    if any(s in leaf for s in _LOWER):
        return "lower"
    return "info"


def _maybe_parse_tail(doc: dict) -> dict:
    """BENCH artifacts wrap the bench's own JSON line in a text tail;
    surface it under ``parsed`` when the runner left it unparsed."""
    if doc.get("parsed") is None and isinstance(doc.get("tail"), str):
        for line in reversed(doc["tail"].strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    doc = dict(doc, parsed=json.loads(line))
                except ValueError:
                    pass
                break
    return doc


def flatten(obj, prefix: str = "", out: dict | None = None) -> dict:
    """Numeric leaves as dotted paths; bool/str leaves dropped, numeric
    lists collapsed to their best value by the key's direction."""
    if out is None:
        out = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            flatten(v, f"{prefix}.{k}" if prefix else str(k), out)
    elif isinstance(obj, list):
        nums = [v for v in obj if isinstance(v, (int, float))
                and not isinstance(v, bool)]
        if nums and len(nums) == len(obj):
            d = direction(prefix)
            if d == "lower":
                out[prefix + ".best"] = min(nums)
            elif d == "higher":
                out[prefix + ".best"] = max(nums)
        else:
            for i, v in enumerate(obj):
                if isinstance(v, (dict, list)):
                    flatten(v, f"{prefix}[{i}]", out)
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        out[prefix] = obj
    return out


def discover(root: str) -> dict[str, list[tuple[int, str]]]:
    fams: dict[str, list[tuple[int, str]]] = {}
    for name in sorted(os.listdir(root)):
        m = _REV_RE.match(name)
        if m:
            fams.setdefault(m.group(1), []).append(
                (int(m.group(2)), os.path.join(root, name))
            )
    return {f: sorted(v) for f, v in fams.items()}


def compare(prev: dict, cur: dict, threshold: float) -> list[dict]:
    rows = []
    for key in sorted(set(prev) & set(cur)):
        a, b = prev[key], cur[key]
        if a == 0:
            continue
        delta = (b - a) / abs(a) * 100.0
        d = direction(key)
        flag = ""
        if d == "lower" and delta > threshold:
            flag = "REGRESSED"
        elif d == "higher" and delta < -threshold:
            flag = "REGRESSED"
        elif d != "info" and abs(delta) > threshold:
            flag = "improved"
        rows.append({
            "key": key, "prev": a, "cur": b,
            "delta_pct": round(delta, 1), "direction": d, "flag": flag,
        })
    return rows


def trend(root: str, threshold: float, family: str = "") -> dict:
    report: dict = {"threshold_pct": threshold, "families": {}}
    for fam, revs in discover(root).items():
        if family and fam != family:
            continue
        if len(revs) < 2:
            report["families"][fam] = {
                "revisions": [r for r, _ in revs], "pairs": [],
                "note": "single revision, nothing to compare",
            }
            continue
        pairs = []
        flat = {
            r: flatten(_maybe_parse_tail(json.load(open(p))))
            for r, p in revs
        }
        for (ra, _), (rb, _) in zip(revs, revs[1:]):
            rows = compare(flat[ra], flat[rb], threshold)
            pairs.append({
                "from": ra, "to": rb,
                "compared": len(rows),
                "regressed": [r for r in rows if r["flag"] == "REGRESSED"],
                "improved": [r for r in rows if r["flag"] == "improved"],
                "rows": rows,
            })
        report["families"][fam] = {
            "revisions": [r for r, _ in revs], "pairs": pairs,
        }
    report["regressions"] = sum(
        len(p["regressed"]) for f in report["families"].values()
        for p in f.get("pairs", [])
    )
    return report


def _fmt(v: float) -> str:
    if isinstance(v, float) and v != int(v):
        return f"{v:.4g}"
    return str(int(v))


def render(report: dict, verbose: bool) -> None:
    th = report["threshold_pct"]
    print(f"banked benchmark trajectory (flagging >{th:g}% bad-direction "
          f"moves; non-gating report)")
    for fam, info in sorted(report["families"].items()):
        revs = "->".join(f"r{r:02d}" for r in info["revisions"])
        if not info.get("pairs"):
            print(f"\n{fam} [{revs}]: {info.get('note', 'no pairs')}")
            continue
        print(f"\n{fam} [{revs}]")
        for pair in info["pairs"]:
            hot = pair["regressed"] + pair["improved"]
            shown = pair["rows"] if verbose else hot
            tag = (f"  r{pair['from']:02d} -> r{pair['to']:02d}: "
                   f"{pair['compared']} shared metrics, "
                   f"{len(pair['regressed'])} regressed, "
                   f"{len(pair['improved'])} improved")
            print(tag)
            if not shown:
                continue
            w = max(len(r["key"]) for r in shown)
            for r in sorted(shown, key=lambda r: -abs(r["delta_pct"])):
                print(f"    {r['key']:<{w}}  {_fmt(r['prev']):>12} -> "
                      f"{_fmt(r['cur']):>12}  {r['delta_pct']:>+7.1f}%  "
                      f"{r['flag']}")
    print(f"\ntotal flagged regressions: {report['regressions']}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=REPO, help="artifact directory")
    ap.add_argument("--threshold", type=float, default=10.0,
                    help="percent move (bad direction) that flags a key")
    ap.add_argument("--family", default="",
                    help="limit to one artifact family, e.g. BENCH")
    ap.add_argument("--verbose", action="store_true",
                    help="print every shared metric, not just flagged ones")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--fail-on-regression", action="store_true",
                    help="exit 1 if any key regressed (default: report only)")
    args = ap.parse_args()

    report = trend(args.root, args.threshold, args.family)
    if args.json:
        print(json.dumps(report, indent=1))
    else:
        render(report, args.verbose)
    if args.fail_on_regression and report["regressions"]:
        print(f"FAIL: {report['regressions']} regressed metrics",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
