"""Share of the traced part of the window in Python's garbage collections
(``py.gc`` spans, one per collection), in percent."""

from bench.spanclock import span_share


def read(ctx):
    return span_share(ctx, {"py.gc"})
