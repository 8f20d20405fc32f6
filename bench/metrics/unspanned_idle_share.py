"""Share of the device's idle time in the traced window that no program
span below the drain explains: idle time whose innermost open span is
``sim.drain`` itself, or none, in percent (``bench/spanclock.py``)."""

from bench.spanclock import NONE, idle_by_span


def read(ctx):
    split = idle_by_span(ctx["trace"]["gaps"], ctx["spans"])
    idle = sum(split.values())
    if not idle:
        return None
    return 100.0 * (split.get(NONE, 0.0) + split.get("sim.drain", 0.0)) / idle
