from benchmark.readers.span_seconds import inside, spans_of, verb_records


def read(ctx, attr: str, names: list | None = None, prefix: str | None = None, span: str | None = None,
         count: str | None = None, scale: float = 1.0, verb: str = "pack"):
    """``scale`` x (sum of attribute ``attr`` over the leaves named in
    ``names``, or whose name starts with ``prefix``) / (sum of attribute
    ``count`` of span ``span``; without one, GiB of tar the records packed),
    over the spans inside the window's completed records of ``verb``: what a
    leaf's thread did (``cpu_s``, ``gc_s``) a counted thing or a GiB. None
    when no such leaf carries ``attr`` (a program whose leaves read no
    usage), nothing is counted, or the ring dropped any."""
    spans = spans_of(ctx)
    if not spans:
        return None
    records = verb_records(ctx, verb)
    spans = inside(spans, records)
    got = [s[4][attr] for s in spans
           if attr in s[4] and (s[0] in names if names is not None else s[0].startswith(prefix))]
    if span is None:
        per = sum(r["bytes"] for r in records) / 2**30
    else:
        per = sum(s[4][count] for s in spans if s[0] == span and count in s[4])
    return scale * sum(got) / per if got and per else None
