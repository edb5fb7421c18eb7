"""Device time by plan stage and mechanism, and the program's host spans,
from a JAX profiler trace of a window.

The program names its device work with `jax.named_scope` (`repro.obs`):
each lowered stage runs under `stage.<kind>.<top operator>`, and inside it
the compaction under `compact`, sorts under `sort`, the PK probe's search
under `probe` and collectives under `wire`.  XLA keeps the scope path in
each HLO instruction's `metadata.op_name`.  A TPU trace's `XLA Ops` events
do not carry it (on a v5e their stats are the device offset and duration
and a time-scale multiplier alone), but each names its instruction, so the
executable's optimized HLO text (`jax.jit(f).lower(...).compile()
.as_text()`) maps it to its scope.  The program's own host spans are
`repro.<name>` annotations (`obs.span`, on only after `obs.enable()`).

`load` reads a `.xplane.pb` into `(start, end, name, scope path)` device
operations, the `repro.*` spans `(start, end, name, depth, thread)` and the
window: the benchmark's `cb.window` span where the trace has one, else
the span of the device operations.  `attribute` then gives, for the
busiest chip:

* seconds by mechanism (`compact`, `sort`, `probe`, `wire`, `other` for
  scoped work under none of them) and by stage, and `unscoped` seconds.
  Each busy instant counts once, under the outermost operation covering it:
  a `while` carries the scope of the code that built the loop and its body
  operations nest inside it.  Mechanism seconds plus `unscoped` equal the
  chip's busy seconds;
* each program span's self seconds (its time less its child spans');
* idle seconds by the innermost program span open over each instant of
  each gap (`none` where no span is open).

    python3 chipbench/stages.py <profile dir or .xplane.pb> --hlo <file>
        [--queries N]

prints the tables; `--hlo` is the program's optimized HLO text, and
`--queries` also divides by the queries in the window.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from typing import Optional

MECHANISMS = ("compact", "sort", "probe", "wire")
STAGE_PREFIX = "stage."
SPAN_PREFIX = "repro."
WINDOW = "cb.window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(path: str) -> tuple:
    """`(stage, mechanism)` of an `op_name` path: the stage component
    (`stage.<kind>.<op>`, or None) and the innermost mechanism scope
    (None when the path holds none)."""
    stage, mech = None, None
    for part in path.split("/"):
        if part.startswith(STAGE_PREFIX) and stage is None:
            stage = part
        elif part in MECHANISMS:
            mech = part
    return stage, mech


def hlo_scopes(hlo_text: str) -> dict:
    """Instruction name -> `op_name` from an optimized HLO module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.-]+) = ", line)
        s = OP_NAME.search(line)
        if m and s:
            out[m.group(1)] = s.group(1)
    return out


def _depths(spans: list, thread) -> list:
    """`(start, end, name, depth, thread)` for the spans of one host
    thread, which nest: depth 0 is outermost."""
    out, stack = [], []
    for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1] <= s:
            stack.pop()
        out.append((s, e, n, len(stack), thread))
        stack.append(e)
    return out


def load(path: str, hlo_text: str) -> dict:
    """Read one `.xplane.pb`: `{"devices": {dev: [(start, end, name,
    scope)]}, "spans": [(start, end, name, depth, thread)], "window":
    (lo, hi)}` (see the module docstring), times in ns; span names without their `repro.` prefix
    (`thread` tells host threads apart: spans nest within one).  `hlo_text`
    is the optimized HLO of the program the window ran; an instruction it
    does not name has the scope ""."""
    from jax.profiler import ProfileData

    from chipbench.trace import op_name

    by_instr = hlo_scopes(hlo_text)
    devices: dict = {}
    spans: list = []
    window = None
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        for thread, line in enumerate(plane.lines):
            if m is not None and line.name == OPS_LINE:
                ops = devices.setdefault(f"TPU:{m.group(1)}", [])
                for e in line.events:
                    name = op_name(e.name)
                    ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                name, by_instr.get(name.partition(":")[0],
                                                   "")))
            elif m is None and plane.name.startswith("/host"):
                mine = []
                for e in line.events:
                    end = e.start_ns + e.duration_ns
                    if e.name == WINDOW:
                        window = (e.start_ns, end)
                    elif e.name.startswith(SPAN_PREFIX):
                        mine.append((e.start_ns, end,
                                     e.name[len(SPAN_PREFIX):]))
                spans.extend(_depths(mine, (id(plane), thread)))
    if window is None:
        ops = [o for v in devices.values() for o in v]
        if not ops:
            raise ValueError(f"no {WINDOW!r} span and no TPU operation in "
                             f"{path}")
        window = (min(o[0] for o in ops), max(o[1] for o in ops))
    return {"devices": devices, "spans": spans, "window": window}


def outermost(ops, lo: float, hi: float) -> list:
    """`(start, end, op)` pieces that partition the union of `ops` inside
    [lo, hi]: each instant belongs to the earliest-starting (on a tie, the
    longest) operation covering it, so an operation nested in another adds
    nothing and one that outlasts its predecessor adds only its tail."""
    pieces: list = []
    covered = lo
    for op in sorted(ops, key=lambda o: (o[0], -o[1])):
        s, e = max(op[0], covered), min(op[1], hi)
        if e > s:
            pieces.append((s, e, op))
            covered = e
    return pieces


def _self_seconds(spans: list, lo: float, hi: float) -> dict:
    """Per span name: seconds inside [lo, hi] less its direct children's
    (`spans` sorted by start)."""
    out: dict = {}
    for i, (s, e, n, d, t) in enumerate(spans):
        own = min(e, hi) - max(s, lo)
        if own <= 0:
            continue
        for s2, e2, _, d2, t2 in spans[i + 1:]:
            if s2 >= e:
                break
            if t2 == t and d2 == d + 1:
                own -= max(0, min(e2, hi) - max(s2, lo))
        out[n] = out.get(n, 0.0) + own * 1e-9
    return out


def _idle_by_span(gaps: list, spans: list) -> dict:
    """Seconds of `gaps` under the innermost span open at each instant."""
    out: dict = {}
    for lo, hi in gaps:
        cuts = sorted({lo, hi} | {t for s, e, *_ in spans
                                  for t in (s, e) if lo < t < hi})
        for a, b in zip(cuts, cuts[1:]):
            open_ = [(d, n) for s, e, n, d, _ in spans if s <= a and e >= b]
            name = max(open_)[1] if open_ else "none"
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def attribute(loaded: dict) -> dict:
    """The busiest chip's busy seconds by mechanism and stage, the program
    spans' self seconds and the idle seconds by program span, all inside
    the window (see the module docstring)."""
    lo, hi = loaded["window"]
    pieces = {d: outermost(ops, lo, hi)
              for d, ops in loaded["devices"].items()}
    if not pieces:
        raise ValueError("the trace holds no TPU operations")
    busy = {d: sum(e - s for s, e, _ in p) * 1e-9 for d, p in pieces.items()}
    dev = max(busy, key=busy.get)
    mech = dict.fromkeys(MECHANISMS + ("other",), 0.0)
    stage: dict = {}
    unscoped = 0.0
    edges = [lo]
    for s, e, op in pieces[dev]:
        dt = (e - s) * 1e-9
        edges += [s, e]
        st, m = scope_of(op[3])
        if st is None and m is None:
            unscoped += dt
            continue
        mech[m or "other"] += dt
        stage[st or "-"] = stage.get(st or "-", 0.0) + dt
    edges.append(hi)
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = sorted(loaded["spans"])
    return {"busiest": dev, "busy_s": busy[dev],
            "window_s": (hi - lo) * 1e-9,
            "mechanism_s": mech, "stage_s": stage, "unscoped_s": unscoped,
            "span_self_s": _self_seconds(spans, lo, hi),
            "idle_by_span_s": _idle_by_span(gaps, spans)}


def table(summary: dict, queries: Optional[int] = None) -> str:
    """The attribution as text: seconds, share of busy time and, with
    `queries`, milliseconds per query."""
    busy = summary["busy_s"]
    rows = [f"busiest {summary['busiest']}: busy {busy!r} s of "
            f"{summary['window_s']!r} s"]

    def block(title, items, share):
        rows.append(title)
        for k, v in sorted(items.items(), key=lambda kv: -kv[1]):
            per = f"  {v / queries * 1e3:.3f} ms/query" if queries else ""
            sh = f"  {v / busy * 100:.3f}%" if share and busy else ""
            rows.append(f"  {k:40s} {v:.6f} s{sh}{per}")

    block("device by mechanism", dict(summary["mechanism_s"],
                                      unscoped=summary["unscoped_s"]), True)
    block("device by stage", summary["stage_s"], True)
    block("program span self time", summary["span_self_s"], False)
    block("device idle by program span", summary["idle_by_span_s"], False)
    return "\n".join(rows)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="a .xplane.pb or a profiler log directory")
    ap.add_argument("--hlo", required=True,
                    help="the program's optimized HLO text, in a file")
    ap.add_argument("--queries", type=int, default=None)
    ap.add_argument("--json", action="store_true",
                    help="print the attribution as one JSON object")
    args = ap.parse_args(argv)
    from chipbench.trace import find_xplane

    path = args.trace if args.trace.endswith(".pb") \
        else find_xplane(args.trace)
    if path is None:
        raise SystemExit(f"no .xplane.pb under {args.trace}")
    with open(args.hlo) as f:
        summary = attribute(load(path, f.read()))
    print(json.dumps(summary) if args.json
          else table(summary, args.queries))
    return 0


if __name__ == "__main__":
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
