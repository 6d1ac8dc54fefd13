from benchmark.readers.span_seconds import inside, spans_of, verb_records


def read(ctx, names: list, num: list, den: list | None = None, verb: str = "pack"):
    """100 x (sum of the attributes ``num``) / (sum of the attributes ``den``,
    or of the leaves' own seconds where ``den`` is None) over the named leaves
    inside the window's completed records of ``verb`` that carry every one of
    them: what a leaf's thread did (``cpu_s``, ``waits``, ``preempts``,
    ``gc_s``) as a share. 0 where the denominator sums to nothing (a thread
    that never left its core was never preempted). None when no such leaf
    carries them (a program whose leaves read no usage) or the ring dropped
    any."""
    spans = spans_of(ctx)
    if not spans:
        return None
    keys = {*num, *(den or ())}
    got = [s for s in inside(spans, verb_records(ctx, verb)) if s[0] in names and keys <= s[4].keys()]
    if not got:
        return None
    total = sum(s[3] - s[2] if den is None else sum(s[4][k] for k in den) for s in got)
    return 100.0 * sum(s[4][k] for s in got for k in num) / total if total else 0.0
