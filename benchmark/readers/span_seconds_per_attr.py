from benchmark.readers.span_seconds import inside, spans_of, verb_records


def read(ctx, names: list, span: str, attr: str, scale: float = 1.0, verb: str = "pack"):
    """``scale`` x (sum of the named leaf spans' seconds) / (sum of attribute
    ``attr`` of span ``span``), both over the spans inside the window's
    completed records of ``verb``: seconds a counted thing (a file, a chunk),
    where ``span_seconds`` gives seconds a GiB. None when no such span was
    recorded, none carries the attribute, it sums to nothing, or the ring
    dropped any."""
    spans = spans_of(ctx)
    if not spans:
        return None
    spans = inside(spans, verb_records(ctx, verb))
    seconds = [s[3] - s[2] for s in spans if s[0] in names]
    count = sum(s[4][attr] for s in spans if s[0] == span and attr in s[4])
    return scale * sum(seconds) / count if seconds and count else None
