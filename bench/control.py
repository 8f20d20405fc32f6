#!/usr/bin/env python3
"""The readings the limits of ``bench/compare.py`` are set from.

For each seed, one whole episode of a cell at its own size:

* ``program``: the timed path (``Simulator`` on the array drain, the
  platform's backend) against the plain reference: the lower readings;
* ``control``: the reference itself with every check-in speed cast to
  float32, the precision below the configuration's float64, in the
  program's place: the upper readings.

Usage (on the chip; ``--cpu`` runs the program on the CPU's backend)::

    python3 bench/control.py --workload even4.r2 --seeds 1,2,3
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np                                  # noqa: E402

from bench import compare, reference, workload      # noqa: E402


def readings(name: str, seed: int, program: bool = True,
             control: bool = True, overrides: dict | None = None,
             root: Path = ROOT) -> dict:
    from bench import run
    cell = run.resolve(run.load_benchmark(root), name)
    ep = workload.make_episode(cell["config"], cell["traffic"], seed,
                               root / "bench", overrides)
    horizon = float(ep["traffic"]["episode_sim_s"])
    t0 = time.perf_counter()
    ref = reference.run_reference(ep, horizon)
    out = {"workload": name, "seed": seed, "grants": len(ref["grants"]),
           "rounds": len(ref["rounds"]),
           "reference_s": time.perf_counter() - t0}
    if program:
        ans = run.play(ep, run.batch_edges(ep["traffic"]), float("inf"),
                       [])[0]
        out["program"] = compare.numbers(ans, ref)
    if control:
        ctl = reference.run_reference(ep, horizon, np.float32)
        out["control"] = compare.numbers(ctl, ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--no-program", action="store_true")
    ap.add_argument("--no-control", action="store_true")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    from bench import run
    if not args.no_program and not args.cpu:
        try:
            run.open_chip(1)
        except run.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 3
    for s in args.seeds.split(","):
        print(json.dumps(readings(args.workload, int(s),
                                  not args.no_program, not args.no_control)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
