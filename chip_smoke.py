"""Smoke run of the main path on a TPU — a smoke run, not a benchmark.

    python chip_smoke.py             # one chip: the pipeline and engine phases
    python chip_smoke.py --chips 4   # four chips: the mesh phase only

Every phase compares its results with the eager numpy reference
(`repro.core.executor`) on the same bindings, as row multisets, and raises
on any mismatch:

* pipeline — TPC-H Q15 at SF1 (6,000,000 lineitem rows, 10,000 suppliers,
  held on the device) through `optimize(flow).compile()` -> `bind_device`
  -> `run_device`, for two batches of different seeds; the second batch
  must not retrace.
* engine — a `DataflowEngine` serving the four demo tenants of
  `repro.launch.serve` at 4,096 rows per request; both its coalesced and
  its solo executables must serve requests, and Q15's run fused spans.
* mesh (`--chips 4` only) — `DistributedPlan` on a 4-device mesh for Q15 at
  SF1 and the combiner flow of `benchmarks/bench_aggregation.py`, each
  compared with eager and with the one-chip pipeline.

The lines before the last report, per phase, the seconds JAX spent
compiling or reading programs back from the persistent compilation cache
(`pipeline.use_compile_cache`), and warm `run_device` seconds bracketed by
`block_until_ready`.  The last line of stdout is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`.
Without a TPU the script raises before any phase and prints no result; it
never falls back to the CPU.  Everything runs in this one process, which
holds the chip.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

SF1_LINEITEM = 6_000_000   # TPC-H SF1; flows.q15 draws rows // 600 suppliers
ENGINE_ROWS = 4096         # per request: Q15's stages fuse at this size
REQUESTS_PER_TENANT = 3    # the first probes solo, the other two coalesce
MESH_CHIPS = 4


def _log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def _check(what: str, got, ref) -> int:
    """Row-multiset equality with the reference; returns the row count."""
    if not got.equivalent(ref):
        raise AssertionError(
            f"{what}: result differs from the reference "
            f"({got.num_valid()} rows vs {ref.num_valid()})")
    return ref.num_valid()


def _timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def phase_pipeline(rows: int = SF1_LINEITEM, seeds=(1, 2)) -> dict:
    """Q15 through the compiled pipeline, two batches, parity + no retrace."""
    import jax

    from repro.configs import flows
    from repro.core import executor
    from repro.core.optimizer import optimize
    from repro.core.pipeline import ExecutableCache

    root, make = flows.q15()
    cp = optimize(root).compile(cache=ExecutableCache())
    report: dict = {"flow": "q15", "lineitem_rows": rows}
    for i, seed in enumerate(seeds):
        batch = make(rows, seed=seed)
        staged = jax.block_until_ready(cp.bind_device(batch))
        traces = cp.cache_stats().traces
        out, first_s = _timed(lambda: cp.run_device(staged))
        retraced = cp.cache_stats().traces - traces
        if i == 0:
            report["first_call_s"] = first_s
            report["routes"] = repr(cp._last_routes)
        elif retraced:
            raise AssertionError(f"pipeline: batch {i} retraced "
                                 f"({retraced} traces)")
        report[f"warm_run_device_s_seed{seed}"] = [
            _timed(lambda: cp.run_device(staged))[1] for _ in range(3)]
        report[f"rows_out_seed{seed}"] = _check(
            f"pipeline seed {seed}", out.to_record_batch(),
            executor.execute(root, batch))
    report["traces"] = cp.cache_stats().traces
    report["parity"] = "eager"
    return report


def phase_engine(rows: int = ENGINE_ROWS,
                 per_tenant: int = REQUESTS_PER_TENANT) -> dict:
    """The four demo tenants through the multi-tenant engine."""
    from repro.core import executor
    from repro.launch.serve import dataflow_tenants
    from repro.serve.dataflow import DataflowEngine, ServeConfig

    tenants = dataflow_tenants()
    eng = DataflowEngine(ServeConfig(async_swap=False))
    for name, root, _ in tenants:
        eng.register(name, root)
    reqs = []
    for i in range(per_tenant):
        for ti, (name, root, make) in enumerate(tenants):
            batch = make(rows, 1000 * ti + i)
            reqs.append((name, root, batch, eng.submit(name, batch)))
    t0 = time.perf_counter()
    eng.drain()
    drain_s = time.perf_counter() - t0
    for name, root, batch, req in reqs:
        _check(f"engine {name}", req.result(timeout=0),
               executor.execute(root, batch))
    stats = eng.stats()
    if not (stats["coalesced_requests"] > 0 and stats["solo_requests"] > 0):
        raise AssertionError(f"engine: coalesced and solo paths must both "
                             f"serve ({stats})")
    # the executables of Q15's plan group that served last: both fused
    q15 = eng._groups[eng._tenants["q15"].group_key]
    routes = {"solo": q15.solo._last_routes,
              "coalesced": q15.coalesced._last_routes}
    for path, r in routes.items():
        if not any(e[0] == "mega" for e in r or ()):
            raise AssertionError(f"engine: q15 {path} ran no fused span "
                                 f"(routes {r})")
    stats["cache"] = dataclasses.asdict(stats["cache"])
    return {"tenants": [t[0] for t in tenants], "rows_per_request": rows,
            "requests": len(reqs), "drain_s": drain_s,
            "q15_routes": repr(routes), "stats": stats, "parity": "eager"}


def phase_mesh(rows: int = SF1_LINEITEM, chips: int = MESH_CHIPS) -> dict:
    """Q15 and the combiner flow on a `chips`-device mesh."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from benchmarks import bench_aggregation as BA
    from repro.configs import flows
    from repro.core import distributed as DX
    from repro.core import executor
    from repro.core.operators import ReduceOp
    from repro.core.optimizer import optimize
    from repro.core.physical import Ctx
    from repro.core.pipeline import ExecutableCache

    devices = jax.devices()[:chips]
    if len(devices) != chips:
        raise AssertionError(f"mesh: {chips} devices wanted, "
                             f"{len(devices)} found")
    mesh = Mesh(np.array(devices), ("data",))
    q15_root, q15_make = flows.q15()
    cases = (("q15", q15_root, q15_make(rows, seed=3)),
             ("combiner", BA.reduce_flow(), BA.bindings(11)))
    stats = DX.shuffle_stats()
    report: dict = {"chips": chips}
    for name, root, batch in cases:
        res = optimize(root, Ctx(dop=chips))
        if name == "combiner" and not any(
                isinstance(n, ReduceOp) and n.combiner
                for n in res.best.flow.iter_nodes()):
            raise AssertionError("mesh: the optimizer chose no combiner")
        dp = DX.DistributedPlan(res.best.plan, mesh=mesh,
                                cache=ExecutableCache())
        staged = dp.bind(batch)
        stats.clear()
        out, first_s = _timed(lambda: dp.run_device(staged))
        placed = len(out.valid.sharding.device_set)
        if dp.p != chips or placed != chips:
            raise AssertionError(f"mesh {name}: ran on {placed} devices "
                                 f"(mesh width {dp.p}), not {chips}")
        got = out.to_record_batch()
        one_chip = optimize(root).compile(cache=ExecutableCache()).run(batch)
        report[name] = {
            "plan": res.best.order(), "first_call_s": first_s,
            "warm_run_device_s": [_timed(lambda: dp.run_device(staged))[1]
                                  for _ in range(3)],
            "wire_bytes": stats.wire_bytes, "dispatches": stats.dispatches,
            "wire_rows": stats.wire_rows, "devices": placed,
            "rows_out": _check(f"mesh {name} vs eager", got,
                               executor.execute(root, batch)),
            "parity": "eager+one_chip"}
        _check(f"mesh {name} vs one-chip pipeline", got, one_chip)
    return report


class _CompileClock:
    """Seconds JAX spends compiling programs or reading them back from the
    persistent cache (the backend-compile event wraps both), and the
    number of persistent-cache hits."""

    def __init__(self):
        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0

    def install(self) -> None:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return self.seconds, self.programs, self.cache_hits

    def since(self, snap: tuple) -> dict:
        return {"compile_s": self.seconds - snap[0],
                "programs_compiled": self.programs - snap[1],
                "persistent_cache_hits": self.cache_hits - snap[2]}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, MESH_CHIPS), default=1,
                    help=f"{MESH_CHIPS}: run the mesh phase only")
    args = ap.parse_args(argv)

    import jax

    from repro.core.pipeline import use_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found "
                         f"{devices[0].platform!r}); this script never falls "
                         f"back to the CPU")
    cache_dir = use_compile_cache()
    clock = _CompileClock()
    clock.install()
    _log(f"smoke run, not a benchmark: jax {jax.__version__}, "
         f"{devices[0].device_kind} x{len(devices)}, "
         f"compile cache {cache_dir}")
    if args.chips == MESH_CHIPS:
        phases = (("mesh", phase_mesh),)
    else:
        phases = (("pipeline", phase_pipeline), ("engine", phase_engine))
    for name, phase in phases:
        snap = clock.snapshot()
        t0 = time.perf_counter()
        report = phase()
        report["phase_s"] = time.perf_counter() - t0
        report.update(clock.since(snap))
        _log(f"{name}: {json.dumps(report)}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
