from benchmark.readers.span_seconds import inside, spans_of, verb_records


def read(ctx, span: str, attr: str, verb: str = "pack"):
    """The largest value of attribute ``attr`` over the named span inside the
    window's completed records of ``verb``. None when no such span carries it
    (a program that does not report it) or the ring dropped any."""
    spans = spans_of(ctx)
    if not spans:
        return None
    got = [s[4][attr] for s in inside(spans, verb_records(ctx, verb)) if s[0] == span and attr in s[4]]
    return max(got) if got else None
