"""95th percentile of the ``venn.replan`` span durations (one span per
VENN-SCHED run), in milliseconds."""

import numpy as np


def read(ctx):
    d = [dur for name, _, dur in ctx["spans"] if name == "venn.replan"]
    if not d:
        return None
    return float(np.percentile(d, 95)) * 1e3
