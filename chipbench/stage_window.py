"""Trace one window of a one-chip pipeline cell's query by hand and reduce
it with `stages.py`: device time by plan stage and by mechanism.

    PYTHONHASHSEED=0 python3 chipbench/stage_window.py --workload <cell> \\
        --seed <n> --queries <q> --out <dir>

From the root of a checkout, on a TPU.  Set-up as `paths/pipeline.py` does it
(`optimize(flow).compile()` -> `bind_device`, two warm-up queries), then
`--queries` queries back to back, at most four in flight, under the JAX
profiler with the program's spans on.  Writes the executable's optimized
HLO text and the trace under `--out` and prints `stages.py`'s tables.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--queries", type=int, default=8)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness as H

    w = H.cell(H.benchmark(), args.workload)
    H.chips_or_refuse(1)
    H.configure_jax()
    import jax

    from repro import obs
    from repro.core.optimizer import optimize
    from repro.core.pipeline import ExecutableCache
    from repro.core.record import batch_from_dict

    cfg = H.load_json("configs", f"{w['config']}.json")
    flows = H.load_module("configs", f"{w['config']}.py")
    data = flows.generate(cfg, args.seed)
    res = optimize(flows.flow(cfg))
    cp = res.compile(cache=ExecutableCache())
    staged = jax.block_until_ready(cp.bind_device(
        {n: batch_from_dict(c) for n, c in data.items()}))
    for _ in range(2):
        jax.block_until_ready(cp.run_device(staged))
    os.makedirs(args.out, exist_ok=True)
    masked, sig = cp._masked_sig(staged)
    hlo = os.path.join(args.out, "program.hlo.txt")
    with open(hlo, "w") as f:
        f.write(cp._executable(sig).lower(masked).compile().as_text())
    trace_dir = os.path.join(args.out, "trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    obs.enable()
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("cb.window"):
        pending = []
        for _ in range(args.queries):
            pending.append(cp.run_device(staged))
            if len(pending) >= 4:
                jax.block_until_ready(pending.pop(0))
        jax.block_until_ready(pending)
    jax.profiler.stop_trace()
    obs.disable()
    print(f"plan {res.best.order()}", flush=True)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "stages.py"),
         trace_dir, "--hlo", hlo, "--queries", str(args.queries)]).returncode


if __name__ == "__main__":
    sys.exit(main())
