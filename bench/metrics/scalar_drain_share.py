"""Share of the traced part of the window in the array drain's scalar tail,
the segments under ``SCALAR_SEG_ROWS`` rows served by per-row ``checkin``
(``sim.drain_scalar`` spans), in percent."""

from bench.spanclock import span_share


def read(ctx):
    return span_share(ctx, {"sim.drain_scalar"})
