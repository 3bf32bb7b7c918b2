"""One run of one cell: set-up, the measured window, the check, the result.

``run_cell`` is everything ``run.py`` does after it has found the chip;
the tests call it on the CPU at tiny sizes.  A driver (``drivers/``)
provides ``Session(model, traffic, seed_rng, workdir, seconds)`` with
``window(seconds) -> Outcome``, ``counters()`` and ``close()``.
"""

from __future__ import annotations

import gc
import math
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from chipbench import artifact, reference, spec, trace_reduce, work


@dataclass
class Outcome:
    """What a driver's window did, and what it produced."""

    metrics: dict[str, float]  # end-to-end values, by name
    attempted: int
    failed: int  # refused or failed (counted in the tails as infinite)
    rows_done: int
    window_s: float
    kernel_call_rows: list[int] = field(default_factory=list)
    # (rows, outputs the timed path returned for them) pairs to compare
    compared: list[tuple[np.ndarray, np.ndarray]] = field(default_factory=list)
    floats: bool = False
    never_came: int = 0  # refused, failed or never answered
    notes: dict = field(default_factory=dict)  # printed on stderr only


@dataclass
class RunRecord:
    """What a per-layer metric's reader may read."""

    cell: spec.Cell
    sizes: work.ModelSizes
    peaks: dict | None
    chips: int
    outcome: Outcome
    counters: dict[str, float]  # after minus before, over the window
    trace: trace_reduce.Trace | None = None
    reduction: trace_reduce.Reduction | None = None


class Workdir:
    """A fixed scratch directory of one cell inside the checkout."""

    def __init__(self, path: Path) -> None:
        shutil.rmtree(path, ignore_errors=True)
        path.mkdir(parents=True)
        self.path = path

    def file(self, name: str) -> Path:
        return self.path / name

    def dir(self, name: str) -> Path:
        p = self.path / name
        p.mkdir(exist_ok=True)
        return p

    def remove(self) -> None:
        shutil.rmtree(self.path, ignore_errors=True)


def seed_streams(seed: int):
    """``seed_rng(k)``: k independent generators drawn from ``--seed``."""
    seq = np.random.SeedSequence(seed % 2**64)
    return lambda k: [np.random.default_rng(s) for s in seq.spawn(k)]


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def margin_err(pairs: list[tuple[np.ndarray, np.ndarray]]) -> float:
    """The widest gap between returned and reference margins, over the
    root mean square of the reference margins: ``(reference, returned)``
    pairs, a reference array counted once however often it recurs."""
    worst, sq, n, seen = 0.0, 0.0, 0, set()
    for ref, got in pairs:
        if id(ref) not in seen:
            seen.add(id(ref))
            sq += float((ref ** 2).sum())
            n += ref.size
        gap = (float(np.abs(got.astype(np.float64) - ref).max())
               if got.shape == ref.shape else math.inf)
        worst = max(worst, gap if math.isfinite(gap) else math.inf)
    rms = math.sqrt(sq / n) if n else 0.0
    return worst / rms if rms > 0 else math.inf


def reference_pairs(model: artifact.Model, outcome: Outcome,
                    precision: str = "exact") -> list[tuple[np.ndarray, np.ndarray]]:
    """(reference margins, returned margins) for every compared block."""
    refs: dict[int, np.ndarray] = {}
    for x, _ in outcome.compared:
        if id(x) not in refs:
            refs[id(x)] = reference.margins(model.trees, x, edges=model.data.get("edges"),
                                            floats=outcome.floats, precision=precision)
    return [(refs[id(x)], got) for x, got in outcome.compared]


def compare(model: artifact.Model, outcome: Outcome) -> dict[str, tuple[float, float]]:
    """Numbers compared with the reference, each with its limit.

    margin_err: see ``margin_err``; missing: requests due in the window
    that were refused (shed), failed or never answered (limit 0).
    """
    return {"margin_err": (margin_err(reference_pairs(model, outcome)),
                           float(model.config["limits"]["margin_err"])),
            "missing": (float(outcome.never_came), 0.0)}


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks, default=0))


def enable_compile_cache(path: Path = artifact.CACHE / "jax") -> None:
    """JAX's persistent compilation cache at one fixed directory inside
    the checkout (the path is part of the cache's key), whatever the
    environment names, caching every program however fast it compiled:
    a warm run then compiles nothing at all."""
    import jax

    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class _Profiler:
    """Start the profiler around the window when ``--trace 1``."""

    def __init__(self, on: bool, out: Path) -> None:
        self.on, self.out = on, out

    def __enter__(self):
        if self.on:
            import jax

            shutil.rmtree(self.out, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the benchmark's spans, not every call
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(self.out), profiler_options=opts)
        return self

    def __exit__(self, *exc) -> None:
        if self.on:
            import jax

            jax.profiler.stop_trace()

    def read(self) -> trace_reduce.Trace:
        found = sorted(self.out.glob("plugins/profile/*/*.xplane.pb"))
        if not found:
            raise RuntimeError(f"profiler wrote no trace under {self.out}")
        return trace_reduce.read_xplane(found[-1])


def run_cell(cell: spec.Cell, *, seed: int, seconds: float,
             trace: bool, t_start: float, root: Path = spec.ROOT,
             cache: Path = artifact.CACHE, compile_cache: bool = True) -> dict:
    """One run; returns the result object that run.py prints last."""
    import jax
    from jax.profiler import TraceAnnotation

    if compile_cache:
        enable_compile_cache(cache / "jax")
    devices = jax.devices()[: cell.chips]
    model, built = artifact.load_or_build(cell.config, cell.config_path, cache)
    log(("built " if built else "loaded ") + artifact.describe(model))
    drv = spec.driver(cell.traffic, root)
    workdir = Workdir(cache / "run" / cell.name)
    session = None
    try:
        session = drv.Session(model, cell.traffic, seed_streams(seed), workdir, seconds)
        before = session.counters()
        # what set-up made lives to the end: the cyclic collector's full
        # passes skip it, as a long-running server's would after warm-up
        gc.freeze()
        setup_s = time.time() - t_start
        log(f"set-up {setup_s:.3f} s")
        profiler = _Profiler(trace, cache / "trace" / cell.name)
        with profiler:
            with TraceAnnotation(trace_reduce.WINDOW_SPAN):
                outcome = session.window(seconds)
        after = session.counters()
        mem_peak = _peak_bytes(devices)
    finally:
        gc.unfreeze()
        if session is not None:
            session.close()
        session = None
        model.compiled = None  # free the program's device state
        gc.collect()
        workdir.remove()
    for k, v in outcome.notes.items():
        log(f"{k}: {v}")

    result: dict = {"correct": False, "attempted": outcome.attempted,
                    "failed": outcome.failed}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    metrics: dict = {}
    if trace:
        rec = RunRecord(cell=cell, sizes=work.sizes_of(model.trees),
                        peaks=work.peaks_for(devices[0].device_kind),
                        chips=cell.chips, outcome=outcome,
                        counters={k: after[k] - before[k] for k in before})
        rec.trace = profiler.read()
        rec.reduction = trace_reduce.reduce(rec.trace)
        device["busy_s"] = rec.reduction.busy_s
        device["window_s"] = rec.reduction.window_s
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"], root).read(rec)
            if value is None:
                log(f"{m['name']}: nothing to read in this run")
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in rec.reduction.device_ops],
            "idle_gaps": [[n, s] for n, s in rec.reduction.idle_gaps]}
    else:
        values = {**outcome.metrics, "setup_s": setup_s}
        for m in cell.end_to_end:
            # a tail that lands on a refused request is infinite: printed as
            # the largest float, since JSON has no infinity
            v = values[m["name"]]
            metrics[m["name"]] = {"value": v if math.isfinite(v) else sys.float_info.max,
                                  "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device

    t0 = time.perf_counter()
    checks = compare(model, outcome)
    log(f"reference check {time.perf_counter() - t0:.3f} s")
    result["correct"] = all(v <= lim for v, lim in checks.values())
    result["checks"] = {k: {"value": v if math.isfinite(v) else None, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k} {v!r} limit {lim!r}")
    return result
