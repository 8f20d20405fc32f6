"""Share of the traced part of the window spent applying the grants of
vectorized matches (``sim.grants`` spans, one per matched segment), in
percent."""

from bench.spanclock import span_share


def read(ctx):
    return span_share(ctx, {"sim.grants"})
