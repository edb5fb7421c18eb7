"""Serving driver: batched token generation for any --arch, or the
multi-tenant dataflow engine (DESIGN.md §11).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
        --requests 8 --max-new 16

    PYTHONPATH=src python -m repro.launch.serve --dataflow \
        --requests 64 --rows 512

`--dataflow` serves a mixed workload (q15 + clickstream + textmining
tenants, plus a drifting q15-shaped tenant) through
`serve.dataflow.DataflowEngine` on a background pump thread and reports
per-tenant throughput, swaps and the engine's cache behavior —
`benchmarks/bench_serving.py` is the measured version of this demo.
"""

from __future__ import annotations

import argparse
import time

import numpy as np
import jax

from ..configs import ARCH_IDS, get_config
from ..models import make_model
from ..serve.engine import Engine, Request


def dataflow_tenants() -> list:
    """The mixed demo workload: `(name, flow, make_bindings(rows, seed))`
    for q15, clickstream, textmining and a q15-shaped tenant whose filter
    hint is 25x off the data (true selectivity 0.04, hint 1.0)."""
    from ..configs import flows

    q15_root, q15_b = flows.q15()
    ck_root, ck_b = flows.clickstream()
    tm_root, tm_b = flows.textmining()
    dr_root, dr_b = flows.q15_drift(hint_selectivity=1.0)
    return [
        ("q15", q15_root, lambda n, s: q15_b(n, seed=s)),
        ("click", ck_root, lambda n, s: ck_b(n, seed=s)),
        ("text", tm_root, lambda n, s: tm_b(n, seed=s)),
        ("drift", dr_root, lambda n, s: dr_b(n, seed=s, true_sel=0.04)),
    ]


def _main_dataflow(args):
    from ..serve.dataflow import DataflowEngine, ServeConfig

    tenants = dataflow_tenants()
    eng = DataflowEngine(ServeConfig(max_coalesce=16, probe_every=8))
    for name, root, _ in tenants:
        eng.register(name, root)

    eng.start()  # pump on a background thread; submissions from this one
    t0 = time.perf_counter()
    reqs = [eng.submit(name, mk(args.rows, 1000 * ti + i))
            for i in range(args.requests)
            for ti, (name, _, mk) in enumerate(tenants)]
    for r in reqs:
        r.result(timeout=300)
    dt = time.perf_counter() - t0
    eng.join_swaps(timeout=60)
    eng.stop()

    lat = np.array([r.latency for r in reqs])
    print(f"[dataflow] {len(reqs)} requests x {args.rows} rows over "
          f"{len(tenants)} tenants in {dt:.2f}s ({len(reqs) / dt:.0f} req/s, "
          f"p99 {np.percentile(lat, 99) * 1e3:.1f}ms)")
    for name, _, _ in tenants:
        print(f"  {name}: {eng.tenant_stats(name)}")
    print(f"  engine: {eng.stats()}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataflow", action="store_true",
                    help="serve the mixed dataflow-tenant demo workload "
                         "instead of token generation")
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--rows", type=int, default=512,
                    help="rows per dataflow request (--dataflow only)")
    args = ap.parse_args()

    from ..core.pipeline import use_compile_cache

    use_compile_cache()
    if args.dataflow:
        _main_dataflow(args)
        return

    cfg = get_config(args.arch, reduced=args.reduced)
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    engine = Engine(model, params, batch_slots=args.slots,
                    max_seq=args.max_seq)

    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, rng.integers(3, 16))
                    .astype(np.int32),
                    max_new_tokens=args.max_new,
                    temperature=args.temperature)
            for _ in range(args.requests)]
    t0 = time.perf_counter()
    engine.generate(reqs)
    dt = time.perf_counter() - t0
    n_tok = sum(len(r.out_tokens) for r in reqs)
    print(f"[serve] {len(reqs)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s)")
    for i, r in enumerate(reqs[:4]):
        print(f"  req{i}: {r.out_tokens}")


if __name__ == "__main__":
    main()
