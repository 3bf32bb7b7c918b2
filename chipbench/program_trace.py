"""The program's own spans in a profiler trace, beside the benchmark's.

``trace_reduce`` keeps the benchmark's spans (``chipbench.*``) alone.
This module reads the same ``.xplane.pb`` again and keeps the program's
spans too (``xtime.*``, opened by ``repro.obs.span``), each with the
metadata it was opened with (the event's stats).  The per-layer readers
of program spans and counters read it:

    host_ms_per_chunk.offline_floats   xtime.score.* stage seconds per chunk
    host_ms_per_flush.online           xtime.serve.flush less its wait
    queue_wait_ms.online               counters on xtime.serve.flush spans
    inbox_wait_ms.online               (differenced over the window)

``breakdown`` charges each idle gap of the device to the innermost span
of either prefix (``trace_reduce.reduce`` over both); ``longest`` lists
the longest spans of a name, such as the interpreter's full collections
(``xtime.gc``).

    python -m chipbench.program_trace TRACE.xplane.pb [--trim OUT.json]

prints the breakdown, the program's stages and the longest full
collections, and can write a trimmed copy for the tests.
"""

from __future__ import annotations

import json
from collections import defaultdict
from dataclasses import dataclass, field
from functools import lru_cache
from pathlib import Path

from chipbench import artifact, trace_reduce

PREFIXES = (trace_reduce.SPAN_PREFIX, "xtime.")
GC_SPAN = "xtime.gc"


@dataclass
class ProgramTrace(trace_reduce.Trace):
    """A ``trace_reduce.Trace`` whose host spans include the program's;
    ``meta`` holds each host span's metadata, in the order of ``host``."""

    meta: list[dict] = field(default_factory=list)

    def __post_init__(self) -> None:
        if not self.meta:
            self.meta = [{} for _ in self.host]

    def to_json(self) -> dict:
        return {**super().to_json(), "meta": self.meta}

    @classmethod
    def from_json(cls, d: dict) -> "ProgramTrace":
        t = trace_reduce.Trace.from_json(d)
        return cls(t.device, t.host, d.get("meta") or [])


def read_xplane(path: str | Path) -> ProgramTrace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    trace = ProgramTrace()
    for plane in data.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PLANE):
            ops = [line for line in plane.lines if line.name == trace_reduce.OP_LINE]
            trace.device[plane.name] = [(trace_reduce.op_name(e.name), e.start_ns, e.end_ns)
                                        for line in ops for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIXES):
                        trace.host.append((e.name, e.start_ns, e.end_ns))
                        trace.meta.append(dict(e.stats) if e.name.startswith("xtime.")
                                          else {})
    return trace


@lru_cache(maxsize=1)
def _read_cached(path: str, mtime_ns: int) -> ProgramTrace:
    return read_xplane(path)


def of_run(rec, cache: Path = artifact.CACHE) -> ProgramTrace | None:
    """The program trace of a run record: ``rec.trace`` itself where it
    is one, else the profiler's file of the record's cell, if its window
    is the record's."""
    if isinstance(rec.trace, ProgramTrace):
        return rec.trace
    if rec.trace is None or rec.cell is None:
        return None
    found = sorted((cache / "trace" / rec.cell.name).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        return None
    trace = _read_cached(str(found[-1]), found[-1].stat().st_mtime_ns)
    try:
        same = trace_reduce.window_of(trace) == trace_reduce.window_of(rec.trace)
    except ValueError:
        return None
    return trace if same else None


def spans(trace: ProgramTrace, name: str) -> list[tuple[float, float, dict]]:
    """``(start_ns, end_ns, meta)`` of every span named ``name`` that
    overlaps the window, clipped to it."""
    w0, w1 = trace_reduce.window_of(trace)
    return [(max(s, w0), min(e, w1), m) for (n, s, e), m in zip(trace.host, trace.meta)
            if n == name and e > w0 and s < w1]


def seconds(trace: ProgramTrace, names) -> float:
    """Summed seconds of the spans named in ``names`` inside the window."""
    return sum(e - s for n in names for s, e, _ in spans(trace, n)) * 1e-9


def counter_delta(trace: ProgramTrace, name: str, by: str, keys) -> dict[str, float]:
    """The change of each counter in ``keys`` between the first and the
    last ``name`` span that starts in the window, per value of its
    ``by`` metadata, summed over those values."""
    w0, w1 = trace_reduce.window_of(trace)
    first: dict = {}
    last: dict = {}
    for (n, s, _), m in zip(trace.host, trace.meta):
        if n != name or not w0 <= s <= w1 or by not in m:
            continue
        k = m[by]
        if k not in first or s < first[k][0]:
            first[k] = (s, m)
        if k not in last or s >= last[k][0]:
            last[k] = (s, m)
    return {c: sum(last[k][1][c] - first[k][1][c] for k in first) for c in keys}


def breakdown(trace: ProgramTrace, top: int = 10) -> trace_reduce.Reduction:
    """``trace_reduce.reduce`` with the program's spans among the host's:
    each idle gap goes to the innermost span of either prefix."""
    return trace_reduce.reduce(trace_reduce.Trace(trace.device, trace.host), top)


def stages(trace: ProgramTrace) -> list[tuple[str, int, float]]:
    """``(name, count, seconds)`` of every program span in the window."""
    count: dict = defaultdict(int)
    secs: dict = defaultdict(float)
    w0, w1 = trace_reduce.window_of(trace)
    for n, s, e in trace.host:
        if n.startswith("xtime.") and e > w0 and s < w1:
            count[n] += 1
            secs[n] += (min(e, w1) - max(s, w0)) * 1e-9
    return sorted(((n, count[n], secs[n]) for n in count), key=lambda x: -x[2])


def longest(trace: ProgramTrace, name: str, top: int = 5) -> list[tuple[float, float]]:
    """``(start_s into the window, ms)`` of the ``top`` longest ``name``
    spans in the window, longest first."""
    w0, _ = trace_reduce.window_of(trace)
    found = sorted(spans(trace, name), key=lambda x: x[0] - x[1])[:top]
    return [((s - w0) * 1e-9, (e - s) * 1e-6) for s, e, _ in found]


def trim(trace: ProgramTrace, keep: int) -> ProgramTrace:
    """The first ``keep`` ops of each plane, and the spans that start
    before the last of them ends (``trace_reduce.trim`` with metadata)."""
    device = {p: sorted(ev, key=lambda x: x[1])[:keep] for p, ev in trace.device.items()}
    end = max(e for ev in device.values() for _, _, e in ev)
    w0, _ = trace_reduce.window_of(trace)
    kept = [(x, m) for x, m in zip(trace.host, trace.meta)
            if x[1] <= end and x[0] != trace_reduce.WINDOW_SPAN]
    host = [x for x, _ in kept] + [(trace_reduce.WINDOW_SPAN, w0, end)]
    return ProgramTrace(device, host, [m for _, m in kept] + [{}])


def main(argv: list[str] | None = None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description="the program's spans in a profiler trace")
    ap.add_argument("xplane")
    ap.add_argument("--trim", help="write a trimmed JSON copy here")
    ap.add_argument("--keep", type=int, default=200)
    args = ap.parse_args(argv)
    trace = read_xplane(args.xplane)
    r = breakdown(trace)
    idle = r.window_s - r.busy_s
    program = sum(s for n, s in r.idle_gaps if n.startswith("xtime."))
    print(json.dumps({
        "window_s": r.window_s, "busy_s": r.busy_s, "idle_s": idle,
        "idle_in_program_spans": program / idle if idle > 0 else None,
        "idle_gaps": r.idle_gaps,
        "stages": stages(trace),
        "gc2_longest": longest(trace, GC_SPAN),
    }, indent=1))
    if args.trim:
        Path(args.trim).write_text(json.dumps(trim(trace, args.keep).to_json()))


if __name__ == "__main__":
    main()
