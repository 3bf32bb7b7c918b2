"""Closed loop of ``score_file`` calls over one seeded ``.npy`` file.

Traffic parameters: ``metric`` (the end-to-end metric's name),
``file_rows`` (rows in the file), ``rows`` ("bins" or "floats": what
the file holds), ``chunk_rows`` (score_file's chunk), ``compare_rows``
(rows of every call compared with the reference, drawn from the seed).

Set-up writes the file from ``--seed`` and warms score_file's one
bucket with one chunk of it.  The window calls ``score_file(model,
path, kind='margin', chunk_rows=...)`` back to back until ``--seconds``
have passed; the last call runs to its end.  The metric is
every row of every call over the time from the window's start to the
last call's end.
"""

from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from chipbench.harness import Outcome, Workdir


class Session:
    def __init__(self, model, traffic: dict, seed_rng, workdir: Workdir,
                 seconds: float) -> None:
        from repro.score import score_file

        self._score_file = score_file
        self.model = model
        self.traffic = traffic
        self.chunk = int(traffic["chunk_rows"])
        self.floats = traffic["rows"] == "floats"
        self.path = workdir.file("rows.npy")
        x = self.reseed(seed_rng, seconds)
        with TraceAnnotation("chipbench.warmup"):
            self._score(x[: self.chunk])

    def reseed(self, seed_rng, seconds: float) -> np.ndarray:
        """Write this seed's file and draw the rows to compare."""
        rows_rng, sample_rng = seed_rng(2)
        t = self.traffic
        x = self.model.rows(rows_rng, int(t["file_rows"]), t["rows"])
        np.save(self.path, x)
        n_cmp = min(int(t["compare_rows"]), x.shape[0])
        self.sample = np.sort(sample_rng.choice(x.shape[0], n_cmp, replace=False))
        self.x_sample = x[self.sample]
        return x

    def _score(self, source):
        return self._score_file(self.model.compiled, source, kind="margin",
                                chunk_rows=self.chunk)

    def counters(self) -> dict:
        return {}

    def window(self, seconds: float) -> Outcome:
        got, calls = [], []
        n_rows = 0
        t0 = time.perf_counter()
        while True:
            with TraceAnnotation("chipbench.score_file"):
                res = self._score(self.path)
            n_rows += res.n_rows
            calls.extend(min(self.chunk, res.n_rows - s)
                         for s in range(0, res.n_rows, self.chunk))
            got.append(np.asarray(res.values)[self.sample])
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        return Outcome(
            metrics={self.traffic["metric"]: n_rows / elapsed},
            attempted=n_rows, failed=0, rows_done=n_rows, window_s=elapsed,
            kernel_call_rows=calls,
            compared=[(self.x_sample, g) for g in got], floats=self.floats,
        )

    def close(self) -> None:
        self.model = None
