"""What the benchmark takes from the program's tracing: the finished spans of
the convert verbs (``nydus_snapshotter_tpu.trace``'s ring) on the clock of the
harness's own records, ``time.perf_counter()``, and the ring's drop count.

Beside ``program.py`` this is the second and last file of the benchmark that
imports ``nydus_snapshotter_tpu`` (``program.py`` could not be edited by the
PR that brought the spans; README.md's "nothing else imports" line waits for a
``benchmark`` issue). Against a program that has no such spans — one whose
spans do not keep a public ``perf_counter`` start — it returns nothing and
does not raise: a reader then finds nothing to read and its metric is left out.
"""

from __future__ import annotations


def finished() -> tuple[list[tuple], int]:
    """-> ([(name, parent, t0, t1, attrs)], spans the ring has dropped).
    ``parent`` is the parent span's name: "" for a root, None where the parent
    is not in the ring (still running, or dropped)."""
    try:
        from nydus_snapshotter_tpu import trace

        raw = trace.snapshot_spans()
        dropped = int(trace.dropped())
    except Exception:  # noqa: BLE001 - no tracing to read is not a fault of the run
        return [], 0
    names = {s.span_id: s.name for s in raw}
    out = []
    for s in raw:
        t0 = getattr(s, "t0", None)
        if t0 is None:
            continue
        parent = names.get(s.parent_id) if s.parent_id else ""
        out.append((s.name, parent, float(t0), float(t0) + s.duration_ms / 1000.0, dict(s.attrs)))
    return out, dropped
