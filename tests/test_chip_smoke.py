"""Rehearsal of ``chip_smoke.py`` on the CPU at a tiny size.

The platform chooses NumPy here, so the test steers the engine onto the
device path (jax backend, Pallas kernels in interpret mode) by patching
``platform_backend``; every phase and check of the chip run then runs as
it would on a TPU."""
import importlib.util
from pathlib import Path

import jax
import pytest

import repro.accel.engine as engine_mod


@pytest.fixture(scope="module")
def chip_smoke():
    path = Path(__file__).resolve().parent.parent / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_cpu(chip_smoke, capsys):
    assert jax.default_backend() == "cpu"
    assert chip_smoke.main() != 0
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_phases_rehearse_on_cpu(chip_smoke, monkeypatch):
    monkeypatch.setattr(engine_mod, "platform_backend", lambda: ("jax", True))
    log = chip_smoke.CompileLog()
    size = (20.0, 40, 0.3)
    recs = {r["phase"]: r for r in chip_smoke.check_phases(
        log, tenx=size, churn=size)}
    assert list(recs) == ["main", "reference", "jnp", "replan_lexsort",
                          "replan_kernel"]
    assert recs["main"]["use_kernel"] and recs["main"]["device_segments"] > 0
    assert recs["jnp"]["backend"] == "jax" and not recs["jnp"]["use_kernel"]
    assert recs["replan_kernel"]["accel.kernel_order_calls"] > 0
