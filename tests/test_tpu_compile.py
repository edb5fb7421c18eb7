"""Ahead-of-time compiles of the chip path against a described TPU v5e.

No chip is attached: the TPU compiler builds for a `v5e:2x2` topology that
is only described, so these tests raise whatever the chip's compiler would
raise, at no chip time.  They cover the executables `chip_smoke.py` runs:

* Q15's fused executable at 4,096 rows (the megakernel span inlined as
  XLA);
* Q15's composed executable at 65,536 rows;
* the combiner flow of `benchmarks/bench_aggregation.py` as one
  `shard_map` program over the 4-device mesh.

The topology is described inside a module fixture, never while a module is
imported: only one process may load the TPU library, and each test worker
imports every test file.  The persistent compilation cache is off around
these compiles, since a compile for a described chip cannot be read back.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import flows
from repro.core.pipeline import ExecutableCache, compile_plan


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _q15_executable(rows: int, use_megakernel: bool):
    root, make = flows.q15()
    cp = compile_plan(root, cache=ExecutableCache(),
                      use_megakernel=use_megakernel)
    masked, sig = cp._bind(make(rows, seed=1))
    return cp._executable(sig), masked, cp._last_routes


def test_q15_fused_span_compiles_as_xla(topo, one_chip):
    fn, masked, routes = _q15_executable(4096, use_megakernel=True)
    assert any(e[0] == "mega" for e in routes), routes
    compiled = fn.lower(_shapes(masked, one_chip)).compile()
    # the span is inlined into the XLA program: no Mosaic kernel in it
    assert "tpu_custom_call" not in compiled.as_text()


def test_q15_composed_compiles(topo, one_chip):
    fn, masked, routes = _q15_executable(65_536, use_megakernel=False)
    assert routes is None
    compiled = fn.lower(_shapes(masked, one_chip)).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 0


def test_combiner_flow_compiles_on_four_chip_mesh(topo):
    from benchmarks import bench_aggregation as BA
    from repro.core import distributed as DX
    from repro.core.operators import ReduceOp
    from repro.core.optimizer import optimize
    from repro.core.physical import Ctx

    mesh = Mesh(np.array(topo.devices), ("data",))
    assert mesh.shape["data"] == 4
    res = optimize(BA.reduce_flow(), Ctx(dop=4))
    assert any(isinstance(n, ReduceOp) and n.combiner
               for n in res.best.flow.iter_nodes())
    dp = DX.DistributedPlan(res.best.plan, mesh=mesh,
                            cache=ExecutableCache())
    staged = dp.bind(BA.bindings(11))
    fn = dp._executable(staged, False)
    shard = NamedSharding(mesh, P("data"))
    args = [_shapes(staged[n], shard) for n in sorted(staged)]
    text = fn.lower(*args).compile().as_text()
    assert "all-gather" in text
