"""Seeded open-loop schedules: the benchmark's own copy of the generator.

``make_trace`` is a copy of ``repro.serve.traffic.make_trace`` (seeded
Lomax arrivals, Zipf popularity over models, 1 + Geometric request
sizes), kept here so that no change to the program moves the yardstick;
a test holds the two bit-identical at a fixed seed.

``schedule`` turns a traffic file's parameters into one run's arrivals.
The set of gaps and sizes comes from the traffic file's own fixed seed,
so every run offers the same work; the run's ``--seed`` only orders
them (and picks the rows).  The gaps are scaled so that the last
request is due exactly at the end of the window.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Mapping, Sequence

import numpy as np


@dataclass(frozen=True)
class TrafficRequest:
    t: float
    model: str
    row_start: int
    n_rows: int


@dataclass(frozen=True)
class TrafficTrace:
    requests: tuple[TrafficRequest, ...]
    seed: int = 0


def make_trace(
    models: Sequence[str] | Mapping[str, int],
    n_requests: int,
    *,
    seed: int,
    mean_interval_s: float = 1e-3,
    tail_alpha: float = 1.8,
    zipf_exponent: float = 1.1,
    mean_rows: float = 1.3,
    max_rows: int = 8,
    stream_len: int = 1 << 30,
) -> TrafficTrace:
    """Seeded heavy-tailed trace (``repro.serve.traffic.make_trace``
    without marks)."""
    if n_requests < 1:
        raise ValueError("n_requests must be >= 1")
    if tail_alpha <= 1.0:
        raise ValueError("tail_alpha must be > 1 (finite mean)")
    if mean_rows < 1.0:
        raise ValueError("mean_rows must be >= 1")
    names = list(models)
    lengths = (
        {m: int(models[m]) for m in names}
        if isinstance(models, Mapping)
        else {m: int(stream_len) for m in names}
    )
    rng = np.random.default_rng(seed)

    # Lomax(alpha) has mean 1/(alpha-1); rescale to the requested mean
    gaps = rng.pareto(tail_alpha, size=n_requests)
    gaps *= mean_interval_s * (tail_alpha - 1.0)
    t = np.cumsum(gaps)

    ranks = np.arange(1, len(names) + 1, dtype=np.float64)
    probs = ranks ** -float(zipf_exponent)
    probs /= probs.sum()
    which = rng.choice(len(names), size=n_requests, p=probs)

    # Geometric(1/mean_rows): mean mean_rows, support {1, 2, ...}, capped
    p = min(1.0, 1.0 / max(mean_rows, 1.0 + 1e-9))
    sizes = np.clip(rng.geometric(p, size=n_requests), 1, max_rows)

    cursor = dict.fromkeys(names, 0)
    requests = []
    for i in range(n_requests):
        model = names[which[i]]
        n = int(sizes[i])
        start = cursor[model] % lengths[model]
        cursor[model] += n
        requests.append(TrafficRequest(float(t[i]), model, start, n))
    return TrafficTrace(tuple(requests), seed)


@dataclass(frozen=True)
class Schedule:
    due_s: np.ndarray  # (n,) offsets from the window's start, ascending
    rows: np.ndarray  # (n,) rows per request

    @property
    def n_rows(self) -> int:
        return int(self.rows.sum())


def schedule(traffic: dict, seconds: float, run_rng: np.random.Generator) -> Schedule:
    """The window's arrivals: ``rate_per_s * seconds`` requests."""
    n = max(1, int(round(float(traffic["rate_per_s"]) * seconds)))
    base = make_trace(["m"], n, seed=int(traffic["base_seed"]),
                      mean_interval_s=1.0 / float(traffic["rate_per_s"]),
                      tail_alpha=float(traffic["tail_alpha"]),
                      mean_rows=float(traffic["mean_rows"]),
                      max_rows=int(traffic["max_rows"]))
    t = np.array([r.t for r in base.requests])
    gaps = np.diff(t, prepend=0.0)
    sizes = np.array([r.n_rows for r in base.requests], dtype=np.int64)
    gaps = run_rng.permutation(gaps)
    due = np.cumsum(gaps)
    due *= seconds / due[-1]
    return Schedule(due_s=due, rows=run_rng.permutation(sizes))
