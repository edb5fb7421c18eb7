"""The benchmark harness: finds a cell's parts by name and runs it once.

Everything a cell needs is found by the names in `BENCHMARK.json`:

* `configs/<config>.json` — the deployment's sizes, source, cuts,
  guarantees and correctness limits; `configs/<config>.py` — the flows as
  users submit them and the seeded data generator; `configs/<config>.ref.py`
  — the plain numpy reference (and its lower-precision control), which
  imports nothing of the program;
* `traffic/<traffic>.json` — the traffic parameters, among them `path`,
  the driver in `paths/<path>.py` that runs them;
* `metrics/<metric>.py` — one reader per per-layer metric.

A driver's `run(r)` gets a `Run`, sets up, measures inside `r.window()`
and returns a `Outcome`; this module turns that into the result line.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import threading
import time
from typing import Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")


# ---------------------------------------------------------------------------
# Discovery by name
# ---------------------------------------------------------------------------
def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(*parts: str):
    """Import a benchmark file by its path (names may hold `-` and `.`)."""
    path = os.path.join(HERE, *parts)
    name = "chipbench_" + "_".join(parts).replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def metrics_for(bench: dict, kind: str, workload: str) -> list:
    """The `end_to_end` or `per_layer` entries this cell reports."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", (workload,))]


def peaks(kind: str) -> dict:
    """Published peaks of one chip, keyed by JAX's `device_kind`; a kind
    that is not in `peaks.json` is an error, never a default."""
    table = load_json("peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# Compilation clock (JAX monitoring events)
# ---------------------------------------------------------------------------
class CompileClock:
    """Seconds JAX spends compiling programs or reading them back from the
    persistent cache (the backend-compile event wraps both), and how many
    programs that was."""

    def __init__(self):
        self.seconds, self.programs = 0.0, 0

    def install(self) -> None:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1


# ---------------------------------------------------------------------------
# One run of one cell
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Outcome:
    """What a driver hands back: end-to-end values, program counters,
    `attempted`/`failed` counts, and `checks` — each compared number as
    `(value, limit)`, correct when value <= limit."""

    end_to_end: dict
    counters: dict
    attempted: int
    failed: int
    checks: dict


class Run:
    """The context a driver runs in: the cell's configuration and traffic,
    the seed and window length, host spans, the compile clock and the
    measured window.  With `control`, the driver compares the reference's
    lower-precision `control` in place of the program's answers."""

    def __init__(self, workload: dict, seed: int, seconds: float,
                 trace: bool, t0: float, devices: list,
                 config: Optional[dict] = None,
                 traffic: Optional[dict] = None, control: bool = False):
        self.workload = workload
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.control = bool(control)
        self.t0 = t0
        name = workload["config"]
        self.config = config if config is not None \
            else load_json("configs", f"{name}.json")
        self.traffic = traffic if traffic is not None \
            else load_json("traffic", f"{workload['traffic']}.json")
        self.flows = load_module("configs", f"{name}.py")
        self.reference = load_module("configs", f"{name}.ref.py")
        self.spans: dict = {}
        self._spans_lock = threading.Lock()
        self.clock = CompileClock()
        self.clock.install()
        self.window_compiles: Optional[int] = None
        self.setup_s: Optional[float] = None
        self.setup_compile_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.memory_peak_bytes: Optional[int] = None
        self.trace_summary: Optional[dict] = None
        self.devices = list(devices)

    def answers(self, outs, data) -> list:
        """What the comparison judges: the program's kept outputs as host
        columns or, with `control`, the control's answer on the same
        tables in their place."""
        from . import compare as C

        if self.control:
            return [self.reference.control(self.config, data)]
        return [C.host_columns(o.to_record_batch()) for o in outs]

    @contextlib.contextmanager
    def span(self, name: str):
        """Host span: seconds accumulate under `name`; while tracing it is
        also a profiler annotation `cb.<name>` on the device clock."""
        t = time.perf_counter()
        if self.trace:
            from jax.profiler import TraceAnnotation

            with TraceAnnotation("cb." + name):
                yield
        else:
            yield
        dt = time.perf_counter() - t
        with self._spans_lock:
            self.spans[name] = self.spans.get(name, 0.0) + dt

    @contextlib.contextmanager
    def window(self, caches=()):
        """The measured window.  Set-up ends where it opens; compilations
        inside it (program traces of `caches` plus backend compiles) are
        counted; with tracing on, the profiler records exactly this span.
        Peak device memory is read as it closes."""
        import jax

        self.setup_s = time.time() - self.t0
        self.setup_compile_s = self.clock.seconds
        traces0 = sum(c.stats().traces for c in caches)
        programs0 = self.clock.programs
        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        t = time.perf_counter()
        try:
            with self.span("window"):
                yield
        finally:
            self.window_s = time.perf_counter() - t
            if self.trace:
                jax.profiler.stop_trace()
            self.window_compiles = (
                sum(c.stats().traces for c in caches) - traces0
                + self.clock.programs - programs0)
            self.memory_peak_bytes = max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in self.devices)
        if self.trace:
            self.trace_summary = reduce_trace()


def reduce_trace() -> dict:
    """Reduce the window's profiler trace and delete it."""
    from . import trace as T

    try:
        path = T.find_xplane(TRACE_DIR)
        if path is None:
            raise FileNotFoundError(f"no trace under {TRACE_DIR}")
        return T.summarize(T.load(path))
    finally:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)


@dataclasses.dataclass
class ReadContext:
    """What a per-layer reader may read."""

    run: Run
    outcome: Outcome
    peaks: dict

    @property
    def trace(self) -> Optional[dict]:
        return self.run.trace_summary


def _number(x):
    """A JSON number; a non-finite reading (a NaN or infinite gap) prints
    as the largest float, beyond every limit, so the line stays JSON."""
    if isinstance(x, int):
        return x
    x = float(x)
    return x if math.isfinite(x) else sys.float_info.max


def result_line(run: Run, outcome: Outcome, bench: dict) -> dict:
    """The contract's last line of standard output."""
    import jax

    name = run.workload["name"]
    devs = jax.devices()
    metrics: dict = {}
    if run.trace:
        ctx = ReadContext(run, outcome, peaks(devs[0].device_kind))
        for m in metrics_for(bench, "per_layer", name):
            value = load_module("metrics", f"{m['name']}.py").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": _number(value),
                                      "unit": m["unit"]}
    else:
        values = dict(outcome.end_to_end, setup_s=run.setup_s)
        for m in metrics_for(bench, "end_to_end", name):
            metrics[m["name"]] = {"value": _number(values[m["name"]]),
                                  "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(run.memory_peak_bytes)}
    line = {"correct": all(v <= lim for v, lim in outcome.checks.values()),
            "attempted": int(outcome.attempted),
            "failed": int(outcome.failed), "metrics": metrics,
            "device": device}
    if run.trace:
        s = run.trace_summary
        device["busy_s"] = s["busy_mean_s"]
        device["window_s"] = s["window_s"]
        line["breakdown"] = {"device_ops": s["device_ops"],
                             "idle_gaps": s["idle_gaps"]}
    line["checks"] = {k: {"value": _number(v), "limit": _number(lim)}
                      for k, (v, lim) in outcome.checks.items()}
    return line


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def chips_or_refuse(chips: int) -> list:
    """The TPU devices the cell runs on; raises where JAX finds no TPU or
    fewer chips than the cell asks for."""
    # the TPU runtime would otherwise log under /tmp, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench: no TPU (JAX found "
                         f"{devs[0].platform!r}); the benchmark never runs "
                         f"on another platform")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"found {len(devs)}")
    return devs[:chips]


def configure_jax() -> None:
    """Persistent compilation cache at the checkout's fixed `.jax_cache`,
    keeping every program, however quick its compile."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()


def main(workload: str, seed: int, seconds: float, trace: bool,
         t0: float, control: bool = False) -> int:
    bench = benchmark()
    w = cell(bench, workload)
    devices = chips_or_refuse(int(w["chips"]))
    configure_jax()
    run = Run(w, seed, seconds, trace, t0, devices, control=control)
    driver = load_module("paths", f"{run.traffic['path']}.py")
    outcome = driver.run(run)
    log(f"setup_s {run.setup_s} window_s {run.window_s} "
        f"window_compiles {run.window_compiles}")
    log(f"counters {json.dumps(outcome.counters)}")
    log(f"spans {json.dumps(run.spans)}")
    line = result_line(run, outcome, bench)
    for k, c in line["checks"].items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0
