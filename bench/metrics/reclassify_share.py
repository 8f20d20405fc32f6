"""Share of the window spent re-classifying the unconsumed tail of a chunk
after the requirement set grew (``Simulator._reclassify``, the program's
``sim.reclassify_wall_s`` counter over the whole window), in percent.
None where the program keeps no such counter."""


def read(ctx):
    s = ctx["counters"].get("sim.reclassify_wall_s")
    if s is None:
        return None
    return 100.0 * s / ctx["window_s"]
