"""Compile the scheduler's device programs for a described TPU v5e.

No chip is attached: the TPU compiler builds each program for a chip that
is only described, and refuses what the chip would refuse (Mosaic layout
and tiling errors, VMEM overuse).  Shapes are the real ones: a full
``SEG_ROWS`` segment at the default candidate cap, a 130-wide candidate
axis, and replan groups of thousands of jobs.  Each test asserts that the
Pallas kernel reached the program as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.accel import _jax_impl
from repro.accel.engine import SEG_ROWS
from repro.accel.kernels import masked_first_fit, schedule_match, segmented_rank


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n,k", [(16384, 32), (1024, 130)])
def test_masked_first_fit_compiles_for_v5e(one_chip, n, k):
    compiled = masked_first_fit.lower(
        _spec((n, k), one_chip), _spec((n, k), one_chip),
        _spec((n,), one_chip), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [2048, 10240])
def test_segmented_rank_compiles_for_v5e(one_chip, n):
    compiled = segmented_rank.lower(
        _spec((n,), one_chip), _spec((n,), one_chip, jnp.float32),
        _spec((n,), one_chip), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_match_jax(one_chip, monkeypatch, n, k, r):
    # the fixed point calls the kernel with the platform's interpret
    # default; in this CPU-backend process that would be the interpreter
    # (cleared traces on both sides keep the patched one private to this test)
    monkeypatch.setattr(schedule_match, "_default_interpret", lambda: False)
    jax.clear_caches()
    try:
        compiled = _jax_impl._match_jax.lower(
            _spec((n * k + r,), one_chip), n, k, use_kernel=True).compile()
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in compiled.as_text()


def test_match_jax_with_kernel_compiles_for_v5e(one_chip, monkeypatch):
    # the one packed input buffer of a full segment at the default cap
    _compile_match_jax(one_chip, monkeypatch, SEG_ROWS, 32, 256)


def test_match_jax_with_kernel_compiles_for_v5e_wide_k(one_chip, monkeypatch):
    _compile_match_jax(one_chip, monkeypatch, SEG_ROWS, 130, 256)
