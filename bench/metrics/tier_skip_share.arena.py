"""Percent of the tier-rounds in the window that the tier loop skipped
because no lane of the program drew the tier (``tier_rounds_skipped``
over ``tier_rounds_run`` + ``tier_rounds_skipped`` of the
``arena.reduce`` spans, mirrored into the trace by the program's
profiler bridge; arena cells).  None where no span carries the counts, as
in a program that does not skip tiers."""

from bench.xscope import _fields, _host_events, _map, _text, trace_path

SPAN = "arena.reduce"
RUN, SKIPPED = "tier_rounds_run", "tier_rounds_skipped"


def span_stats(path: str) -> list:
    """(start ns, {stat: value}) of every host event named ``SPAN``."""
    with open(path, "rb") as f:
        buf = f.read()
    out = []
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:                                  # XSpace.planes
            continue
        name, lines, metadata, stat_spans = "", [], [], []
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = _text(buf, v)
            elif f == 3:
                lines.append(v)
            elif f == 4:
                metadata.append(v)
            elif f == 5:
                stat_spans.append(v)
        if not name.startswith("/host:"):
            continue
        stat_names, wanted = {}, set()
        for span in stat_spans:
            key, value = _map(buf, span)
            stat_names[key] = next((_text(buf, v) for f, v in
                                    _fields(buf, *value) if f == 2), "")
        for span in metadata:
            key, value = _map(buf, span)
            if any(f == 2 and _text(buf, v) == SPAN
                   for f, v in _fields(buf, *value)):
                wanted.add(key)
        if wanted:
            out += _host_events(buf, lines, wanted, stat_names)
    return out


def read(ctx):
    path = trace_path(ctx)
    if path is None:
        return None
    run = skipped = 0
    for t, stats in span_stats(path):
        if ctx.lo <= t < ctx.hi:
            run += int(stats.get(RUN) or 0)
            skipped += int(stats.get(SKIPPED) or 0)
    if run + skipped == 0:
        return None
    return 100.0 * skipped / (run + skipped)
