"""Run every benchmark (one per paper table/figure) and print a summary.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only a,b] [--list]

Benchmarks with committed perf baselines (enumeration, pipeline) have their
rows persisted as BENCH_<name>.json at the repo root so the perf trajectory
is tracked across PRs.  Full runs maintain the committed baselines; --quick
runs (CI smoke) write BENCH_<name>.quick.json next to them so they never
clobber the cross-PR trajectory — benchmarks/check_regression.py compares
the two and gates CI on slowdowns.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# benchmarks whose summaries are persisted as cross-PR baselines
_BASELINED = ("enumeration", "pipeline", "aggregation", "adaptive", "serving",
              "distributed")


def baseline_path(name: str, quick: bool) -> str:
    suffix = ".quick.json" if quick else ".json"
    return os.path.join(_REPO_ROOT, f"BENCH_{name}{suffix}")


def _write_baseline(name: str, summary: dict, quick: bool) -> None:
    doc = {
        "bench": name,
        "quick": quick,
        "python": platform.python_version(),
        "machine": platform.machine(),
    }
    for k, v in summary.items():
        if k not in ("name", "wall_s"):
            doc[k] = v
    path = baseline_path(name, quick)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller data / fewer repeats")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--list", action="store_true",
                    help="print available benchmark names and exit")
    args = ap.parse_args()

    from repro.core.pipeline import use_compile_cache

    use_compile_cache()
    from . import (bench_adaptive, bench_aggregation, bench_clickstream,
                   bench_distributed, bench_enumeration, bench_pipeline,
                   bench_q7, bench_q15, bench_roofline, bench_sca,
                   bench_serving, bench_textmining)

    benches = {
        "q7": bench_q7, "q15": bench_q15, "textmining": bench_textmining,
        "clickstream": bench_clickstream, "sca": bench_sca,
        "enumeration": bench_enumeration, "pipeline": bench_pipeline,
        "aggregation": bench_aggregation, "adaptive": bench_adaptive,
        "serving": bench_serving, "roofline": bench_roofline,
        "distributed": bench_distributed,
    }
    if args.list:
        for name in benches:
            print(name)
        return
    if args.only:
        wanted = args.only.split(",")
        unknown = [w for w in wanted if w not in benches]
        if unknown:
            sys.exit(f"unknown benchmark(s) {unknown}; "
                     f"available: {','.join(benches)}")
        benches = {k: v for k, v in benches.items() if k in wanted}

    summaries = []
    for name, mod in benches.items():
        t0 = time.perf_counter()
        try:
            s = mod.run(quick=args.quick)
        except Exception as e:  # pragma: no cover
            s = {"name": name, "error": repr(e)}
        s["wall_s"] = round(time.perf_counter() - t0, 2)
        if name in _BASELINED and "error" not in s:
            _write_baseline(name, s, args.quick)
            s = {k: v for k, v in s.items() if k != "rows"}
        summaries.append(s)

    print("\n==== summary ====")
    for s in summaries:
        print(s)
    if any("error" in s for s in summaries):
        sys.exit(1)


if __name__ == "__main__":
    main()
