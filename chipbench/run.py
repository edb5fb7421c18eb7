"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell (configuration, traffic, chips) is
looked up in `BENCHMARK.json`.  The run loads and warms up (set-up), then
measures for `--seconds`; with `--trace 1` it records the window with the
JAX profiler and reports the per-layer metrics instead of the end-to-end
ones.  The last line of standard output is one JSON object (`correct`,
`attempted`, `failed`, `metrics`, `device`, and last `checks`: each number
compared with the reference beside its limit); the checks are also the last
lines of standard error.  Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.

`--control 1` runs the cell with the configuration's lower-precision
control (`control` of `configs/<config>.ref.py`) compared in the program's
place: its result line must read `"correct": false`.  The benchmark's own
runs leave it at 0.

Python's hash seed is pinned (the process re-executes itself once with
`PYTHONHASHSEED=0` before JAX is imported), because some of the program's
flows trace differently under different hash seeds, and a warm run must
find every program in the persistent compilation cache (`.jax_cache/` in
the checkout).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T0_ENV = "CHIPBENCH_T0"   # process start, carried across the re-exec


def _start_time() -> float:
    """Wall-clock start of this run: before the hash-seed re-exec, if any."""
    now = time.time()
    try:
        t0 = float(os.environ.pop(T0_ENV))
    except (KeyError, ValueError):
        return now
    return t0 if 0.0 <= now - t0 < 60.0 else now


def main(argv=None) -> int:
    t0 = _start_time()
    if os.environ.get("PYTHONHASHSEED") != "0":
        os.environ["PYTHONHASHSEED"] = "0"
        os.environ[T0_ENV] = repr(t0)
        os.execv(sys.executable, [sys.executable] + sys.argv)
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness

    return harness.main(args.workload, args.seed, args.seconds,
                        bool(args.trace), t0, bool(args.control))


if __name__ == "__main__":
    sys.exit(main())
