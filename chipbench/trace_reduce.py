"""Reduce a profiler trace to device busy, idle, kernel time and a breakdown.

A trace is read into plain lists (``Trace``), so the reduction runs the
same on a trace just recorded on the chip and on the trimmed one that
the tests keep (``fixtures/``):

    device  {plane: [(op name, start_ns, end_ns), ...]}
            the op-level line ("XLA Ops") of every TPU plane, each op
            by its HLO instruction name (``op_name``);
    host    [(span name, start_ns, end_ns), ...]
            the benchmark's own ``jax.profiler.TraceAnnotation`` spans
            (names starting "chipbench."), from every host thread.

The window is the "chipbench.window" span.  Per device plane, busy time
is the union of its op intervals inside the window; the result averages
it over the planes.  Each idle gap inside the window is charged to the
innermost benchmark span open at its midpoint (the latest-started one).
Kernel time (``kernel_time``) sums the ops whose name holds a kernel's
name; the per-layer readers pass the name.

    python chipbench/trace_reduce.py TRACE.xplane.pb [--trim OUT.json]

prints what the trace holds (planes, lines, event counts, the most
frequent op names) and can write a trimmed copy for the tests.
"""

from __future__ import annotations

import bisect
import json
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"

Interval = tuple[str, float, float]


@dataclass
class Trace:
    device: dict[str, list[Interval]] = field(default_factory=dict)
    host: list[Interval] = field(default_factory=list)

    def to_json(self) -> dict:
        return {"device": self.device, "host": self.host}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({k: [tuple(e) for e in v] for k, v in d["device"].items()},
                   [tuple(e) for e in d["host"]])


def op_name(text: str) -> str:
    """An op event's instruction name: TPU traces name an op by its whole
    HLO text (``%name = type op(operands...)``), whose operands can name
    other ops."""
    return text.split(" = ", 1)[0].lstrip("%")


def read_xplane(path: str | Path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    trace = Trace()
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = [line for line in plane.lines if line.name == OP_LINE]
            trace.device[plane.name] = [
                (op_name(e.name), e.start_ns, e.end_ns) for line in ops for e in line.events]
        elif plane.name.startswith("/host:"):
            trace.host.extend(
                (e.name, e.start_ns, e.end_ns) for line in plane.lines
                for e in line.events if e.name.startswith(SPAN_PREFIX))
    return trace


@dataclass(frozen=True)
class Reduction:
    window_s: float
    busy_s: float  # averaged over device planes
    device_ops: list[tuple[str, float]]  # top ops by total seconds
    idle_gaps: list[tuple[str, float]]  # idle seconds by host span
    n_devices: int

    @property
    def idle_pct(self) -> float | None:
        """Share of the window in which no op ran, in percent."""
        return 100.0 * (1.0 - self.busy_s / self.window_s) if self.window_s > 0 else None


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window_of(trace: Trace) -> tuple[float, float]:
    spans = [(s, e) for n, s, e in trace.host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _span_at(spans: list[Interval], starts: list[float], t: float,
             lookback: int = 256) -> str:
    """The latest-started span (not the window) open at time t, among the
    ``lookback`` spans that started last before it."""
    i = bisect.bisect_right(starts, t)
    for name, _, e in reversed(spans[max(0, i - lookback):i]):
        if e >= t:
            return name
    return WINDOW_SPAN


def _inside(trace: Trace, events: list[Interval]) -> list[Interval]:
    w0, w1 = window_of(trace)
    return [(n, max(s, w0), min(e, w1)) for n, s, e in events if e > w0 and s < w1]


def kernel_time(trace: Trace, kernel: str) -> tuple[float, int]:
    """Seconds and events of the ops whose name holds ``kernel`` inside
    the window, summed over the device planes."""
    ns, calls = 0.0, 0
    for events in trace.device.values():
        for n, s, e in _inside(trace, events):
            if kernel in n:
                ns += e - s
                calls += 1
    return ns * 1e-9, calls


def reduce(trace: Trace, top: int = 10) -> Reduction:
    w0, w1 = window_of(trace)
    if not trace.device:
        raise ValueError("trace has no device plane")
    spans = sorted((x for x in trace.host if x[0] != WINDOW_SPAN), key=lambda x: x[1])
    starts = [s for _, s, _ in spans]
    busy_total = 0.0
    op_time: Counter = Counter()
    idle: defaultdict = defaultdict(float)
    for events in trace.device.values():
        inside = _inside(trace, events)
        for n, s, e in inside:
            op_time[n] += e - s
        merged = _union([(s, e) for _, s, e in inside])
        busy_total += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                idle[_span_at(spans, starts, (gs + ge) / 2)] += ge - gs
    n_dev = len(trace.device)
    return Reduction(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy_total / n_dev * 1e-9,
        device_ops=[(n, t * 1e-9) for n, t in op_time.most_common(top)],
        idle_gaps=[(n, t / n_dev * 1e-9) for n, t in
                   sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
        n_devices=n_dev,
    )


def _describe(path: str) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = Counter(e.name for e in evs).most_common(8)
            print(f"  line {line.name!r}: {len(evs)} events; top names {names}")
            for e in evs[:2]:
                try:
                    stats = {k: str(v)[:80] for k, v in e.stats}
                except (TypeError, ValueError):
                    stats = {}
                print(f"    e.g. {e.name!r} start {e.start_ns} dur {e.duration_ns} stats {stats}")


def trim(trace: Trace, keep: int) -> Trace:
    """The first ``keep`` ops of each plane, and the spans they overlap."""
    device = {p: sorted(ev, key=lambda x: x[1])[:keep] for p, ev in trace.device.items()}
    end = max(e for ev in device.values() for _, _, e in ev)
    host = [x for x in trace.host if x[1] <= end]
    w0, _ = window_of(trace)
    host = [x for x in host if x[0] != WINDOW_SPAN] + [(WINDOW_SPAN, w0, end)]
    return Trace(device, host)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description="describe or trim a profiler trace")
    ap.add_argument("xplane")
    ap.add_argument("--trim", help="write a trimmed JSON copy here")
    ap.add_argument("--keep", type=int, default=200)
    args = ap.parse_args()
    _describe(args.xplane)
    if args.trim:
        Path(args.trim).write_text(json.dumps(trim(read_xplane(args.xplane), args.keep).to_json()))
