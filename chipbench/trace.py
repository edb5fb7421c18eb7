"""Reduce a JAX profiler trace to the benchmark's device numbers.

A traced run records the window with `jax.profiler` and hands the
`.xplane.pb` file to `load`, which keeps three things on one clock (the
profiler aligns device timestamps to the host's):

* the harness's window span (`cb.window`);
* the operations of each TPU, from the `XLA Ops` line of every
  `/device:TPU:<n>` plane (what the chip runs and waits on), and its
  asynchronous copies and transfers from the `Async XLA Ops` line, each
  named `<instruction>:<opcode>`;
* the harness's own host spans (`cb.<name>`, written with
  `jax.profiler.TraceAnnotation`), from every host plane.

`summarize` then gives, inside the window: each chip's busy seconds (the
union of its `XLA Ops` intervals), the part of them during which a
collective operation (synchronous or asynchronous) is in progress,
the operations that took most device time, and the longest gaps of the
busiest chip, each named after the host span that overlaps it most.
`Trace.to_json`/`from_json` keep a reduced trace as a small fixture.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Optional

SPAN_PREFIX = "cb."
WINDOW_SPAN = "window"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"all-gather|all-to-all|all-reduce|collective-permute|reduce-scatter"
    r"|^send|^recv")


def op_name(text: str) -> str:
    """`<instruction>:<opcode>` from an operation's HLO text, e.g.
    `while.19:while` from `%while.19 = (u32[], ...) while(...), ...`."""
    head, eq, rest = text.partition(" = ")
    if not eq:
        return text
    rest = rest.lstrip()
    if rest.startswith("("):            # a tuple-shaped result
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:].lstrip()
    else:
        rest = rest.partition(" ")[2]
    opcode = rest.partition("(")[0].strip()
    return f"{head.lstrip('%')}:{opcode}" if opcode else head.lstrip("%")


def is_collective(name: str) -> bool:
    """An operation that moves data between chips, by its instruction or
    its opcode (`op_name` form)."""
    return any(COLLECTIVE.search(part) for part in name.split(":"))


@dataclasses.dataclass
class Trace:
    """Intervals in nanoseconds: `devices` maps a device to its operations
    `(start, end, name)` and `async_ops` to its asynchronous ones; `host`
    lists the harness spans `(start, end, name)` with the prefix stripped,
    the window span among them."""

    devices: dict
    host: list
    async_ops: dict = dataclasses.field(default_factory=dict)

    def window(self) -> tuple:
        spans = [(s, e) for s, e, n in self.host if n == WINDOW_SPAN]
        if len(spans) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                             f"{len(spans)}")
        return spans[0]

    def to_json(self) -> dict:
        def ops(by_dev):
            return {d: [list(o) for o in v] for d, v in by_dev.items()}

        return {"devices": ops(self.devices), "async_ops": ops(self.async_ops),
                "host": [list(h) for h in self.host]}

    @staticmethod
    def from_json(obj: dict) -> "Trace":
        def ops(by_dev):
            return {d: [tuple(o) for o in v] for d, v in by_dev.items()}

        return Trace(ops(obj["devices"]), [tuple(h) for h in obj["host"]],
                     ops(obj.get("async_ops", {})))


def load(path: str) -> Trace:
    """Read one `.xplane.pb` into a `Trace` (TPU planes and harness spans
    only; everything else in the file is skipped)."""
    from jax.profiler import ProfileData

    devices: dict = {}
    async_ops: dict = {}
    host: list = []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is not None:
                into = {OPS_LINE: devices, ASYNC_LINE: async_ops}.get(
                    line.name)
                if into is None:
                    continue
                ops = into.setdefault(f"TPU:{m.group(1)}", [])
                ops.extend((e.start_ns, e.start_ns + e.duration_ns,
                            op_name(e.name)) for e in line.events)
            elif plane.name.startswith("/host"):
                host.extend((e.start_ns, e.start_ns + e.duration_ns,
                             e.name[len(SPAN_PREFIX):])
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return Trace(devices, host, async_ops)


def busy_union(ops, lo: float, hi: float) -> list:
    """Merged `(start, end)` intervals of `ops`, clipped to [lo, hi]."""
    merged: list = []
    for s, e, _ in sorted(ops):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _overlap(a: list, b: list) -> float:
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _gaps(busy, lo: float, hi: float) -> list:
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


class _SpanIndex:
    """Host spans (window excluded) sorted by start, for overlap queries."""

    def __init__(self, host):
        self.spans = sorted((s, e, n) for s, e, n in host
                            if n != WINDOW_SPAN)
        self.starts = [s for s, _, _ in self.spans]
        self.longest = max((e - s for s, e, _ in self.spans), default=0)

    def doing(self, lo: float, hi: float) -> str:
        """The span covering most of [lo, hi], or "none"."""
        best, best_ov = "none", 0.0
        i = bisect.bisect_left(self.starts, hi)
        while i > 0:
            i -= 1
            s, e, n = self.spans[i]
            if s < lo - self.longest:
                break
            ov = min(e, hi) - max(s, lo)
            if ov > best_ov:
                best, best_ov = n, ov
        return best


def summarize(trace: Trace, top: int = 10) -> dict:
    """The window's device numbers (seconds).  `busy_s`/`collective_s` are
    per device (`collective_s` is busy time with a collective in
    progress); `busiest` names the chip with the most busy time, whose
    gaps `idle_gaps` lists; `device_ops` sums each operation name over all
    chips."""
    lo, hi = trace.window()
    busy_s, coll_s, by_op = {}, {}, {}
    unions = {}
    for dev, ops in trace.devices.items():
        unions[dev] = busy_union(ops, lo, hi)
        busy_s[dev] = sum(e - s for s, e in unions[dev]) * 1e-9
        coll = [o for o in ops + trace.async_ops.get(dev, [])
                if is_collective(o[2])]
        coll_s[dev] = _overlap(unions[dev], busy_union(coll, lo, hi)) * 1e-9
        for s, e, n in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                by_op[n] = by_op.get(n, 0.0) + d * 1e-9
    if not busy_s:
        raise ValueError("the trace holds no TPU operations")
    busiest = max(busy_s, key=busy_s.get)
    index = _SpanIndex(trace.host)
    gaps = sorted(_gaps(unions[busiest], lo, hi),
                  key=lambda g: g[1] - g[0], reverse=True)[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_s,
        "busy_mean_s": sum(busy_s.values()) / len(busy_s),
        "collective_s": coll_s,
        "busiest": busiest,
        "device_ops": [[n, s] for n, s in sorted(
            by_op.items(), key=lambda kv: kv[1], reverse=True)[:top]],
        "idle_gaps": [[index.doing(s, e), (e - s) * 1e-9] for s, e in gaps],
    }


def find_xplane(root: str) -> Optional[str]:
    """The newest `.xplane.pb` under a profiler log directory."""
    import glob
    import os

    found = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None
