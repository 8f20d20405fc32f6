"""Each test's traced runs keep their profiler trace in a directory of
their own: the harness's fixed ``.bench_out/trace`` is one directory for
every process, and the test files run in parallel processes, so one run
could delete another's trace."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@pytest.fixture(autouse=True)
def _own_trace_dir(monkeypatch, tmp_path):
    from bench import run
    monkeypatch.setattr(run, "OUT", tmp_path / "bench_out")
