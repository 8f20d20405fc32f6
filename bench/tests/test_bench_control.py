"""The comparison that decides ``correct`` separates the program from its
control at a size a test run holds: the timed path (here on the CPU's
backend) reads 0 on every number for every seed, and the reference run in
float32 speeds, the precision below the configuration's, fails.  Each cell
of ``BENCHMARK.json`` runs at the sizes of its traffic file's ``control``
block."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import compare, control      # noqa: E402
from bench.tests.test_bench_rehearsal import traffic_blocks  # noqa: E402

SMALL = traffic_blocks("control")


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_program_reads_zero_and_control_fails(cell):
    assert SMALL[cell], f"{cell}: no control block in its traffic file"
    for seed in (1, 2, 3**20):
        r = control.readings(cell, seed, overrides=SMALL[cell])
        assert r["rounds"] > 0
        assert r["program"] == dict.fromkeys(compare.LIMITS, 0), r
        assert any(r["control"][k] > compare.LIMITS[k]
                   for k in compare.LIMITS), r
