from benchmark import program_spans

# per -> (the verb whose records bound the window's spans, its root span, the divisor)
PER = {
    "gib": ("pack", "convert.pack", lambda records: sum(r["bytes"] for r in records) / 2**30),
    "image": ("merge", "convert.merge", len),
}
ROOT_SELF = "<root self>"


def spans_of(ctx) -> list | None:
    """The program's finished spans, or None when the ring dropped any (sums
    over a ring with holes would read low). A ctx that carries ``spans``
    (``(spans, dropped)``, hand-made in tests) is read instead of the ring."""
    spans, dropped = ctx["spans"] if "spans" in ctx else program_spans.finished()
    return None if dropped else spans


def verb_records(ctx, verb: str) -> list:
    return [r for r in ctx["records"] if r["verb"] == verb and r["ok"]]


def inside(spans: list, records: list) -> list:
    """The spans lying inside the [t0, t1] of one of the records."""
    return [s for s in spans if any(r["t0"] <= s[2] and s[3] <= r["t1"] for r in records)]


def read(ctx, names: list, per: str = "gib", when: str = "window"):
    """Sum of the named leaf spans' seconds. ``when="window"``: the spans
    inside the window's completed ``pack`` records, per GiB of tar packed
    (``per="gib"``), or inside its ``merge`` records, per merge
    (``per="image"``). ``when="setup"``: the spans that ended before the
    window's first record, as plain seconds. ``names=["<root self>"]``: the
    verbs' root spans less the leaves under them (what no leaf covers).
    None when no such span was recorded or the ring dropped any."""
    spans = spans_of(ctx)
    if not spans or not ctx["records"]:
        return None
    if when == "setup":
        start = min(r["t0"] for r in ctx["records"])
        got = [s[3] - s[2] for s in spans if s[0] in names and s[3] <= start]
        return sum(got) if got else None
    verb, root, divisor = PER[per]
    records = verb_records(ctx, verb)
    spans = inside(spans, records)
    if names == [ROOT_SELF]:
        roots = [s[3] - s[2] for s in spans if s[0] == root]
        got = [sum(roots) - sum(s[3] - s[2] for s in spans if s[1] == root)] if roots else []
    else:
        got = [s[3] - s[2] for s in spans if s[0] in names]
    return sum(got) / divisor(records) if got else None
