"""From a profiler trace (``*.xplane.pb``) to device busy / idle seconds.

Uses only JAX (``jax.profiler.ProfileData``). What a v5e trace holds, as seen
by hand in PR 25: one plane per chip, ``/device:TPU:<n>``, whose line
``XLA Ops`` has one event per executed HLO op (ops inside a ``while`` lie
inside the ``while``'s own event, so busy time is the UNION of intervals,
never their sum) and whose line ``XLA Modules`` has one event per program run
(``jit__pass1(...)``, ``jit__pass2(...)``); the host's ``TraceAnnotation``
spans are on plane ``/host:CPU`` on the same clock.

    python3 benchmark/trace_reduce.py <file.xplane.pb>   # print the reduction
"""

from __future__ import annotations

import json
import sys
from array import array

import numpy as np

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union_seconds(starts: np.ndarray, ends: np.ndarray) -> tuple[float, np.ndarray, np.ndarray]:
    """Length of the union of [start, end) intervals (ns in, seconds out) and
    the merged intervals themselves."""
    if len(starts) == 0:
        return 0.0, starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    new = np.concatenate([[True], s[1:] > e[:-1]])  # a gap before this interval
    ms = s[new]
    me = e[np.concatenate([new[1:], [True]])]
    return float((me - ms).sum()) / 1e9, ms, me


NAMED_FROM_NS = 20_000  # ops shorter than 20 us are never among the top ten; their names are not read


def _events(line, named_from_ns: float = 0.0) -> tuple[list[str], np.ndarray, np.ndarray]:
    """(names, starts, ends) of a line. A 1 MiB-chunk pack is tens of millions
    of op events, and reading an event's name (its whole HLO text) costs more
    than its times: names of events shorter than ``named_from_ns`` stay ""."""
    names, starts, durs = [], array("d"), array("d")
    for ev in line.events:
        dur = ev.duration_ns
        starts.append(ev.start_ns)
        durs.append(dur)
        names.append(ev.name if dur >= named_from_ns else "")
    starts, durs = np.frombuffer(starts, np.float64), np.frombuffer(durs, np.float64)
    return names, starts, starts + durs


def reduce_file(path: str, **kw) -> dict:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path), **kw)


def reduce(data, span_prefixes: tuple[str, ...] = ("pack:", "merge"), top: int = 10) -> dict:
    """A trace (``jax.profiler.ProfileData``) -> busy_s (mean over chips),
    window_s (first to last host span), the top device ops, the longest idle
    gaps named by the host span they fall in and the programs on either side,
    and the trace's structure."""
    structure, spans, chips = {}, [], []
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            if plane.name.startswith(DEVICE_PLANE) or plane.name == HOST_PLANE:
                lines[line.name] = _events(line, NAMED_FROM_NS if line.name == OPS_LINE else 0.0)
        structure[plane.name] = {name: len(ev[0]) for name, ev in lines.items()}
        if plane.name == HOST_PLANE:
            for names, starts, ends in lines.values():
                spans += [(n, s, e) for n, s, e in zip(names, starts, ends) if n.startswith(span_prefixes)]
        elif plane.name.startswith(DEVICE_PLANE) and OPS_LINE in lines:
            chips.append((lines[OPS_LINE], lines.get(MODULES_LINE, ([], np.zeros(0), np.zeros(0)))))
    if not chips or not spans:
        return {"structure": structure}
    w0, w1 = min(s for _n, s, _e in spans), max(e for _n, _s, e in spans)
    busy, op_seconds, program_seconds, gaps = [], {}, {}, []
    for (names, starts, ends), (mod_names, mod_starts, mod_ends) in chips:
        inside = (ends > w0) & (starts < w1)
        s, e = np.clip(starts[inside], w0, w1), np.clip(ends[inside], w0, w1)
        seconds, ms, me = union_seconds(s, e)
        busy.append(seconds)
        for i in np.flatnonzero(inside & (ends - starts >= NAMED_FROM_NS)):
            key = _short_op(names[i])
            op_seconds[key] = op_seconds.get(key, 0.0) + float(min(ends[i], w1) - max(starts[i], w0)) / 1e9
        for name, m0, m1 in zip(mod_names, mod_starts, mod_ends):
            if m1 > w0 and m0 < w1:
                key = f"program {_short(name)}"
                program_seconds[key] = program_seconds.get(key, 0.0) + (min(m1, w1) - max(m0, w0)) / 1e9
        edges_s, edges_e = np.concatenate([[w0], me]), np.concatenate([ms, [w1]])
        mods = sorted(zip(mod_starts, mod_ends, mod_names))
        for g0, g1 in zip(edges_s, edges_e):
            if g1 - g0 <= 1e6:  # 1 ms: shorter gaps lie between ops of one program
                continue
            # a program's event starts a little before its first op and ends a little after its last
            before = next((n for s_, _e, n in reversed(mods) if s_ <= g0), "start")
            after = next((n for s_, _e, n in mods if s_ > g0), "end")
            for name, s0, s1 in _split_by_span(g0, g1, spans):  # a gap is cut where a host span ends
                gaps.append((f"{name}: {_short(before)} -> {_short(after)}", (s1 - s0) / 1e9))
    gap_seconds = {}
    for name, dur in gaps:
        gap_seconds[name] = gap_seconds.get(name, 0.0) + dur
    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": float(np.mean(busy)), "window_s": (w1 - w0) / 1e9, "chips": len(chips),
            # whole programs first, then single ops (an op inside a `while` is also in the `while`)
            "device_ops": (rank(program_seconds) + rank(op_seconds))[:top], "idle_gaps": rank(gap_seconds),
            "spans": [[n, (e - s) / 1e9] for n, s, e in sorted(spans, key=lambda x: x[1])],
            "structure": structure}


def _short(program: str) -> str:
    return program.split("(")[0]


def _short_op(hlo: str) -> str:
    """'%fusion.3 = s32[131072]{0:T(1024)} fusion(...), kind=kLoop' -> '%fusion.3 fusion s32[131072]'."""
    name, _, rest = hlo.partition(" = ")
    if not rest:
        return name[:80]
    if rest.startswith("("):  # a tuple shape: skip to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = "tuple", rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
        shape = shape.split("{")[0]
    return f"{name} {rest.split('(')[0]} {shape}"


def _split_by_span(g0: float, g1: float, spans) -> list[tuple[str, float, float]]:
    """The pieces of [g0, g1) by the host span each lies in."""
    pieces, covered = [], 0.0
    for name, s, e in spans:
        lo, hi = max(g0, s), min(g1, e)
        if hi > lo:
            pieces.append((name, lo, hi))
            covered += hi - lo
    if g1 - g0 - covered > 1e6:
        pieces.append(("between verbs", g0, g0 + (g1 - g0 - covered)))
    return pieces


if __name__ == "__main__":
    print(json.dumps(reduce_file(sys.argv[1]), indent=1))
