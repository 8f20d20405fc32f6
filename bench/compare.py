"""The comparison that decides ``correct``.

Every replay the window drove is held to the plain reference
(``bench/reference.py``) run once over the same episode: a whole replay to
the whole reference, a replay the window cut off to the reference's records
up to the simulated time the replay reached.  Three numbers, each with the
limit 0 (the comparison is exact):

* ``grants_differ``: grants ``(check-in time, job, round)`` that differ,
  position by position, plus the difference in their count;
* ``rounds_differ``: completed round records (job, round, submit, fill,
  completion time, demand, responses, failures, retries) that differ;
* ``finishes_differ``: jobs whose finish time differs.
"""
from __future__ import annotations

import numpy as np

from bench.reference import run_reference

LIMITS = {"grants_differ": 0, "rounds_differ": 0, "finishes_differ": 0}


def _differ(a, b) -> int:
    return sum(1 for x, y in zip(a, b) if x != y) + abs(len(a) - len(b))


def upto(ref: dict, t: float) -> dict:
    """The reference's records up to simulated time ``t``."""
    return {"grants": [g for g in ref["grants"] if g[0] <= t],
            "rounds": [r for r in ref["rounds"] if r[4] <= t],
            "finished": {j: f for j, f in ref["finished"].items() if f <= t}}


def numbers(got: dict, want: dict) -> dict:
    fg, fw = got["finished"], want["finished"]
    return {
        "grants_differ": _differ(got["grants"], want["grants"]),
        "rounds_differ": _differ(got["rounds"], want["rounds"]),
        "finishes_differ": sum(1 for j in set(fg) | set(fw)
                               if fg.get(j) != fw.get(j)),
    }


def check(ep: dict, episodes, speed_dtype=np.float64):
    """``(checks, failed)`` for the window's replays ``(answers, reached,
    complete)``: each number summed over the replays, beside its limit, and
    the count of grants that differ."""
    horizon = float(ep["traffic"]["episode_sim_s"])
    if not any(c for _, _, c in episodes):
        horizon = max(r for _, r, _ in episodes)
    ref = run_reference(ep, horizon, speed_dtype)
    total = dict.fromkeys(LIMITS, 0)
    for ans, reached, complete in episodes:
        want = ref if complete else upto(ref, reached)
        for k, v in numbers(ans, want).items():
            total[k] += v
    checks = {k: {"value": total[k], "limit": LIMITS[k]} for k in LIMITS}
    return checks, total["grants_differ"]
