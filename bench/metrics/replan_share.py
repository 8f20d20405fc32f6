"""Share of the window spent in VENN-SCHED replans
(``VennScheduler._reschedule``, the program's ``venn.replan_wall_s``
counter over the whole window), in percent.  None where the program keeps
no such counter."""


def read(ctx):
    s = ctx["counters"].get("venn.replan_wall_s")
    if s is None:
        return None
    return 100.0 * s / ctx["window_s"]
