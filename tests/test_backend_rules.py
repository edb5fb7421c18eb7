"""Backend rules of the chip path, checked on the CPU.

A fused span dispatches as XLA unless `REPRO_MEGAKERNEL_PALLAS=1` forces the
interpret-mode Pallas wrapper, which a TPU backend refuses; `use_kernels`
raises on a TPU backend at every entry point; and the persistent compile
cache lands where `JAX_COMPILATION_CACHE_DIR` says, else at one fixed path.
The TPU backend is steered here by patching `jax.default_backend`.
"""

from __future__ import annotations

import os

import pytest

import jax

from repro.configs import flows
from repro.core import pipeline as PL
from repro.core.optimizer import optimize
from repro.kernels import megakernel as MK


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_dispatch_mode_is_xla_unless_forced(monkeypatch):
    monkeypatch.delenv(MK.PALLAS_ENV, raising=False)
    assert MK.dispatch_mode() == "xla"
    monkeypatch.setenv(MK.PALLAS_ENV, "0")
    assert MK.dispatch_mode() == "xla"
    monkeypatch.setenv(MK.PALLAS_ENV, "1")
    assert MK.dispatch_mode() == "pallas"


def test_dispatch_mode_on_tpu(monkeypatch, on_tpu):
    monkeypatch.delenv(MK.PALLAS_ENV, raising=False)
    assert MK.dispatch_mode() == "xla"
    monkeypatch.setenv(MK.PALLAS_ENV, "1")
    with pytest.raises(NotImplementedError, match="64-bit types"):
        MK.dispatch_mode()


def _engine(use_kernels):
    from repro.serve.dataflow import DataflowEngine, ServeConfig

    return DataflowEngine(ServeConfig(use_kernels=use_kernels))


def _mesh_plan(use_kernels):
    from repro.core.distributed import DistributedPlan

    return DistributedPlan(optimize(flows.q15()[0]).best.plan,
                           use_kernels=use_kernels)


@pytest.mark.parametrize("entry", [
    lambda uk: PL.compile_plan(flows.q15()[0], use_kernels=uk),
    lambda uk: optimize(flows.q15()[0]).compile(use_kernels=uk),
    _engine,
    _mesh_plan,
], ids=["compile_plan", "RankedPlan.compile", "DataflowEngine",
        "DistributedPlan"])
def test_use_kernels_refused_on_tpu(entry, on_tpu):
    with pytest.raises(NotImplementedError, match="sorted_probe"):
        entry(True)
    entry(False)  # the XLA path stays open


def test_use_kernels_runs_interpreted_off_tpu():
    root, make = flows.q15()
    b = make(512, seed=5)
    out = PL.compile_plan(root, use_kernels=True,
                          cache=PL.ExecutableCache()).run(b)
    from repro.core import executor

    assert out.equivalent(executor.execute(root, b))


@pytest.fixture
def cache_config():
    """Restore JAX's compile-cache directory after the test."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_compile_cache_honours_env(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv(PL.COMPILE_CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert PL.use_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; no other directory is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_path(monkeypatch, cache_config):
    monkeypatch.delenv(PL.COMPILE_CACHE_ENV, raising=False)
    first, second = PL.use_compile_cache(), PL.use_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == second == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
