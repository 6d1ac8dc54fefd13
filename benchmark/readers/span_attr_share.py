from benchmark.readers.span_seconds import inside, spans_of, verb_records


def read(ctx, span: str, num: str, den: str, verb: str = "pack"):
    """100 x (sum of attribute ``num``) / (sum of attribute ``den``) over the
    named span inside the window's completed records of ``verb``. None when
    no such span carries both or the ring dropped any."""
    spans = spans_of(ctx)
    if not spans:
        return None
    got = [s[4] for s in inside(spans, verb_records(ctx, verb)) if s[0] == span and num in s[4] and den in s[4]]
    total = sum(a[den] for a in got)
    return 100.0 * sum(a[num] for a in got) / total if total else None
