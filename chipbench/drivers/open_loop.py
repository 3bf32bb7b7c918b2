"""Open loop of requests into a ``ClusterServer`` at a fixed offered rate.

Traffic parameters: ``rate_per_s``, ``tail_alpha``, ``mean_rows``,
``max_rows`` and ``base_seed`` (the arrivals, see ``traffic_gen``),
``rows`` ("bins" or "floats"), ``replicas``, ``flush_rows``,
``max_batch``, ``max_queue_rows`` and ``heartbeat_timeout_s`` (the
server), ``warm_s`` (warm-up traffic before the window),
``compare_rows`` (rows of requests compared with the reference, drawn
from the seed before the window).

Set-up starts the server with ``kind='margin'`` and the Pallas backend,
registers the model (which compiles every serving bucket on every
replica) and runs ``warm_s`` of the same traffic.  In the window the
main thread submits each request at its due time; a collector thread
waits on the handles in submission order through ``ClusterHandle.result``
and stamps each completion.  A request's latency runs from its due time
to that stamp, so it can overstate a request by as much as an earlier
request was still running.  A refused (shed) or failed request counts
as infinitely late, and as one that never came in the check that
decides ``correct`` (``missing``, limit 0): every request due in the
window has to be answered.  ``latency_p50_ms`` (a cell's metric) and
``latency_p99_ms`` (which the sweep in ``tools.py`` reads) are
nearest-rank percentiles over every request due in the window.
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np
from jax.profiler import TraceAnnotation

from chipbench import traffic_gen
from chipbench.harness import Outcome, Workdir

# a request not answered this long after the window closed never came
GRACE_S = 60.0


class Session:
    def __init__(self, model, traffic: dict, seed_rng, workdir: Workdir,
                 seconds: float) -> None:
        from repro.core.deploy import DeployConfig
        from repro.serve.batching import MicroBatcher
        from repro.serve.cluster import ClusterServer

        self.name = model.config["name"]
        self.traffic = traffic
        self.kind = traffic["rows"]
        self.compare_rows = int(traffic["compare_rows"])
        self.server = None
        # compile every serving program before a replica runs: a cold
        # compile inside register() kept both replicas from beating their
        # heartbeat past its timeout on the chip, and the server failed
        # them over (PERF.md, Open questions)
        with TraceAnnotation("chipbench.precompile"):
            MicroBatcher.for_engine(model.compiled.engine(), kind="margin",
                                    max_batch=int(traffic["max_batch"])).warm()
        self.server = ClusterServer(
            n_replicas=int(traffic["replicas"]), deploy=DeployConfig(backend="pallas"),
            kind="margin", flush_rows=int(traffic["flush_rows"]),
            max_batch=int(traffic["max_batch"]),
            max_queue_rows=int(traffic["max_queue_rows"]),
            heartbeat_timeout_s=float(traffic["heartbeat_timeout_s"]),
            run_dir=str(workdir.dir("cluster")),
        )
        with TraceAnnotation("chipbench.register"):
            self.server.register(self.name, model.compiled)
        self.model = model
        warm_rng = self.reseed(seed_rng, seconds)
        warm_s = float(traffic["warm_s"])
        if warm_s > 0:
            warm = traffic_gen.schedule(traffic, warm_s, warm_rng)
            with TraceAnnotation("chipbench.warmup"):
                self._drive(warm, model.rows(warm_rng, warm.n_rows, self.kind))
        report = self.server.report(self.name)
        if report["failovers"] or any(r["state"] != "alive"
                                      for r in report["replicas"].values()):
            raise RuntimeError(f"replica failover during set-up: {report['replicas']}")

    def reseed(self, seed_rng, seconds: float) -> np.random.Generator:
        """This seed's arrivals, rows and compared requests; returns the
        warm-up's generator."""
        sched_rng, rows_rng, sample_rng, warm_rng = seed_rng(4)
        self.sched = traffic_gen.schedule(self.traffic, seconds, sched_rng)
        self.x = self.model.rows(rows_rng, self.sched.n_rows, self.kind)
        self.keep = self._draw_sample(sample_rng)
        return warm_rng

    def _draw_sample(self, rng: np.random.Generator) -> np.ndarray:
        """Requests to compare, drawn before the window: the longest one,
        then others in a seeded order up to ``compare_rows`` rows.  Only
        their handles outlive the collector, so the client keeps few
        objects alive for the interpreter's cyclic collector to scan."""
        rows = self.sched.rows
        longest = int(np.argmax(rows))
        order = rng.permutation(len(rows))
        order = order[order != longest]
        total = rows[longest] + np.cumsum(rows[order])
        n_more = int(np.searchsorted(total, self.compare_rows)) + 1
        return np.sort(np.concatenate([[longest], order[:n_more]])).astype(np.int64)

    def counters(self) -> dict:
        reps = self.server.report(self.name)["replicas"].values()
        return {"served_rows": sum(r["served_rows"] for r in reps),
                "flushes": sum(r["flushes"] for r in reps)}

    def _drive(self, sched: traffic_gen.Schedule, x: np.ndarray,
               keep: np.ndarray = np.zeros(0, np.int64)) -> dict:
        from repro.serve.cluster import FailedRequest, ShedError

        n = len(sched.due_s)
        start = np.concatenate([[0], np.cumsum(sched.rows)])
        done_at = np.full(n, np.nan)
        late = np.zeros(n)
        wanted = set(keep.tolist())
        kept: dict = {}  # answered requests among ``keep``: index -> handle
        never: list[int] = []
        failed: list[int] = []
        pending: queue.SimpleQueue = queue.SimpleQueue()
        t0 = time.perf_counter()
        deadline = t0 + sched.due_s[-1] + GRACE_S

        def collect() -> None:
            while (item := pending.get()) is not None:
                i, h = item
                try:
                    h.result(timeout=max(0.0, deadline - time.perf_counter()))
                    done_at[i] = time.perf_counter()
                    if i in wanted:
                        kept[i] = h
                except TimeoutError:
                    never.append(i)
                except FailedRequest:
                    failed.append(i)

        collector = threading.Thread(target=collect, name="chipbench-collect")
        collector.start()
        shed = 0
        try:
            for i in range(n):
                due = t0 + sched.due_s[i]
                delay = due - time.perf_counter()
                if delay > 0:
                    with TraceAnnotation("chipbench.wait_due"):
                        time.sleep(delay)
                late[i] = time.perf_counter() - due
                with TraceAnnotation("chipbench.submit"):
                    try:
                        h = self.server.submit(self.name, x[start[i]:start[i + 1]])
                    except ShedError:
                        shed += 1
                        continue
                pending.put((i, h))
        finally:
            pending.put(None)
            with TraceAnnotation("chipbench.collect"):
                collector.join()
        t_end = time.perf_counter()
        return {"t0": t0, "t_end": t_end, "done_at": done_at, "late": late,
                "kept": kept, "never": never, "failed": failed, "shed": shed,
                "start": start}

    def window(self, seconds: float) -> Outcome:
        r = self._drive(self.sched, self.x, self.keep)
        lat = (r["done_at"] - (r["t0"] + self.sched.due_s)) * 1e3
        lat[np.isnan(lat)] = np.inf  # shed, failed or never answered
        self.last_latencies_ms = lat
        p50, p99 = (float(np.percentile(lat, q, method="inverted_cdf")) for q in (50, 99))
        n_fail = int(np.isinf(lat).sum())
        answered = np.flatnonzero(np.isfinite(lat))
        rows, got = self._sample(r)
        late = r["late"] * 1e3
        notes = {"generator lateness ms (p50, p99, max)":
                 [float(np.percentile(late, 50)), float(np.percentile(late, 99)),
                  float(late.max())],
                 "requests, rows, shed, failed, never answered":
                 [len(lat), self.sched.n_rows, r["shed"], len(r["failed"]), len(r["never"])]}
        return Outcome(
            metrics={"latency_p99_ms": p99, "latency_p50_ms": p50},
            attempted=len(lat), failed=n_fail,
            rows_done=int(self.sched.rows[answered].sum()),
            window_s=r["t_end"] - r["t0"],
            compared=[(rows, got)] if len(rows) else [],
            floats=self.kind == "floats",
            never_came=r["shed"] + len(r["failed"]) + len(r["never"]),
            notes=notes,
        )

    def _sample(self, r: dict) -> tuple[np.ndarray, np.ndarray]:
        """Rows and returned margins of the drawn requests that were answered."""
        picked = [i for i in self.keep.tolist() if i in r["kept"]]
        if not picked:
            return np.zeros((0, self.x.shape[1]), self.x.dtype), np.zeros((0, 0))
        s = r["start"]
        rows = np.concatenate([self.x[s[i]:s[i + 1]] for i in picked])
        got = np.concatenate([np.asarray(r["kept"][i].result(timeout=0)) for i in picked])
        return rows, got

    def close(self) -> None:
        if self.server is not None:
            self.server.close()
            self.server = None
        self.model = None
