"""Share of its roofline that the masked first-fit kernel reached: the
least time the chip could take for the work the traced calls define (live
rows x candidate slots, read against HBM bandwidth; ``bench/roofline.py``),
over the kernel's device time in the trace, in percent.

Each program execution in the trace is matched to the last device call
the host made before it started; a call's kernel runs once per
fixed-point pass, and each run is counted with that call's work."""

from bisect import bisect_right

from bench import roofline
from bench.tracereduce import kernels_per_module


def read(ctx):
    red = ctx["trace"]
    ktime = sum(d for _, d in red["kernel_events"])
    calls = sorted(ctx["calls"])
    if not ktime or not calls:
        return None
    peak = roofline.peaks(ctx["device_kind"])
    starts = [c[0] for c in calls]
    bound = 0.0
    for (start, _), runs in zip(red["modules"], kernels_per_module(red)):
        i = bisect_right(starts, start) - 1
        if runs and i >= 0:
            _, rows, k = calls[i]
            bound += runs * roofline.bound_s(
                roofline.masked_first_fit_bytes(rows, k), peak)
    return 100.0 * bound / ktime
