"""Profiler spans and stage counters of the offline pipeline and the
serving tier (repro.obs): the spans under a real ``jax.profiler`` trace,
the per-stage counters that add up to each request's latency, the
full-collection hook, and the completed-request ring behind
``ClusterServer.stats()``."""

import gc
from collections import deque

import jax
import numpy as np
import pytest

from repro.api import build
from repro.core.quantize import FeatureQuantizer
from repro.core.trees import random_deep_ensemble
from repro.obs import GC2
from repro.score import score_file
from repro.serve import ClusterServer, make_trace, replay_trace
from repro.serve.loop import LatencyStats, RequestRecord

pytestmark = pytest.mark.filterwarnings("ignore:pallas TPU support unavailable")


@pytest.fixture(scope="module")
def model():
    """A small binary model with a feature grid, float rows and their bins."""
    ens = random_deep_ensemble(n_trees=8, depth=4, n_features=5, n_bins=32, seed=5)
    xf = np.random.default_rng(7).normal(size=(203, 5))
    fq = FeatureQuantizer.fit(xf, n_bins=32)
    return build(ens, quantizer=fq), xf, fq.transform(xf).astype(np.int32)


def _traced(tmp_path, fn):
    """Run ``fn`` inside a ``test.call`` span under the profiler; returns
    its result and the host spans named ``xtime.*`` or ``test.*`` as
    ``(name, start_ns, end_ns, stats, thread)``, by start."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test.call"):
            out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    events = [(e.name, e.start_ns, e.end_ns, dict(e.stats), (plane.name, i))
              for plane in data.planes if plane.name.startswith("/host:")
              for i, line in enumerate(plane.lines) for e in line.events
              if e.name.startswith(("xtime.", "test."))]
    return out, sorted(events, key=lambda x: x[1])


@pytest.mark.parametrize("rows", ["floats", "bins"])
def test_score_file_stages_under_the_profiler(tmp_path, model, rows):
    cm, xf, xb = model
    x = xf if rows == "floats" else xb
    res, events = _traced(tmp_path, lambda: score_file(cm, x, chunk_rows=64))
    assert res.n_chunks == 4
    (call,) = [e for e in events if e[0] == "test.call"]
    names = [n for n, *_ in events if n.startswith("xtime.")]
    prepare = ["read", "bin", "pad", "dispatch"] if rows == "floats" else ["read", "pad", "dispatch"]
    drain = ["wait", "fetch", "write"]
    # chunk 0 is prepared and dispatched; each later one is dispatched
    # before the one ahead of it drains; the last read finds no chunk
    want = ["bind", *prepare, *[s for _ in range(3) for s in prepare + drain], "read", *drain]
    assert names == [f"xtime.score.{s}" for s in want]
    spans = [e for e in events if e[0].startswith("xtime.")]
    for (_, _, end, *_), (_, start, *_) in zip(spans, spans[1:]):
        assert end <= start  # one thread, one stage at a time
    assert all(call[1] <= s and e <= call[2] for _, s, e, *_ in spans)


def _serve(srv, xb, n, seed):
    trace = make_trace(["m"], n, seed=seed, mean_interval_s=2e-4, mean_rows=1.5)
    res = replay_trace(srv.submit, trace, {"m": xb}, speed=0)
    srv.drain(timeout=60)
    return res


def _stage_sums(rep):
    reps = rep["replicas"].values()
    return (rep["queue_wait_s"], sum(r["inbox_wait_s"] for r in reps),
            sum(r["service_s"] for r in reps))


def test_stage_counters_add_up_to_latency_and_survive_reset(model):
    cm, _, xb = model
    with ClusterServer(n_replicas=2, flush_rows=16, max_batch=128,
                       heartbeat_timeout_s=5.0) as srv:
        srv.register("m", cm)
        _serve(srv, xb, 150, seed=3)
        rep = srv.report("m")
        ring = srv._records
        at = ring.select("m")
        latency = float((ring.t_done[at] - ring.t_enqueue[at]).sum())
        assert rep["measured"]["requests"] == 150 == rep["routed_requests"]
        assert sum(r["started_requests"] for r in rep["replicas"].values()) == 150
        assert sum(_stage_sums(rep)) == pytest.approx(latency, rel=1e-9)
        assert all(s > 0 for s in _stage_sums(rep))
        srv.reset_stats()
        after = srv.report("m")
        assert after["measured"]["requests"] == 0
        assert _stage_sums(after) == _stage_sums(rep)
        assert after["routed_requests"] == 150
        _serve(srv, xb, 40, seed=4)
        more = srv.report("m")
        assert more["routed_requests"] == 190 and more["measured"]["requests"] == 40
        assert all(b > a for a, b in zip(_stage_sums(rep), _stage_sums(more)))
        assert srv.report()["routed_requests"] == 190  # every model's


def test_serving_spans_under_the_profiler(tmp_path, model):
    cm, _, xb = model
    with ClusterServer(n_replicas=2, flush_rows=16, max_batch=128,
                       heartbeat_timeout_s=5.0) as srv:
        srv.register("m", cm)

        def work():
            _serve(srv, xb, 120, seed=5)
            gc.collect()  # one full collection, under its own span

        _, events = _traced(tmp_path, work)
        rep = srv.report("m")
    flushes = [e for e in events if e[0] == "xtime.serve.flush"]
    assert len(flushes) == sum(r["flushes"] for r in rep["replicas"].values())
    children = ["coalesce", "pad", "dispatch", "wait", "fetch", "complete"]
    for name, s, e, meta, thread in flushes:
        inside = [n for n, cs, ce, _, t in events
                  if t == thread and n != name and s <= cs and ce <= e]
        assert inside == [f"xtime.serve.{c}" for c in children]
        assert meta["model"] == "m" and meta["rows"] >= 1
        assert {"routed_requests", "queue_wait_s", "started_requests",
                "inbox_wait_s"} <= set(meta)
    # the counters on the spans grow with each flush of a replica
    for rid in rep["replicas"]:
        started = [m["started_requests"] for *_, m, _ in flushes if m["replica"] == rid]
        assert started == sorted(started)
    routes = [e for e in events if e[0] == "xtime.serve.route"]
    assert 1 <= len(routes) <= rep["routed_requests"]
    # no span per request: the flush, its six stages and the routes
    served = [e for e in events if e[0].startswith("xtime.serve.")]
    assert len(served) == 7 * len(flushes) + len(routes)
    assert [e[0] for e in events].count("xtime.gc") >= 1


def test_full_collections_counted_while_a_server_is_open():
    on = GC2._on_gc
    with ClusterServer(n_replicas=1) as a:
        with ClusterServer(n_replicas=1) as b:
            assert gc.callbacks.count(on) == 1  # once per process
            before = b.report()["gc2_passes"]
            gc.collect()
            rep = b.report()
            assert rep["gc2_passes"] == before + 1 and rep["gc2_s"] > 0
            gc.collect(1)  # a younger generation is not counted
            assert b.report()["gc2_passes"] == before + 1
        assert gc.callbacks.count(on) == 1
        assert a.report()["gc2_passes"] >= 1
    assert on not in gc.callbacks


def test_ring_stats_equal_records_on_a_fixed_stream(model):
    """``stats()`` and ``report()["measured"]`` from the ring equal
    ``LatencyStats.from_records`` over the same completed requests, kept
    as records in a deque of the same capacity, once the ring wraps."""
    cm, _, xb = model
    with ClusterServer(n_replicas=2, flush_rows=16, max_batch=128,
                       heartbeat_timeout_s=5.0, history=50) as srv:
        kept: deque = deque(maxlen=50)
        add = srv._records.add

        def spy(name, rows, t_enqueue, t_done):
            kept.extend(RequestRecord(name, -1, r, t, t_done)
                        for r, t in zip(rows, t_enqueue))
            add(name, rows, t_enqueue, t_done)

        srv._records.add = spy
        srv.register("m", cm)
        srv.register("n", cm)
        trace = make_trace(["m", "n"], 130, seed=6, mean_interval_s=2e-4, mean_rows=1.5)
        replay_trace(srv.submit, trace, {"m": xb, "n": xb}, speed=0)
        srv.drain(timeout=60)
        assert srv._records.n == 50
        for name in (None, "m", "n"):
            records = [r for r in kept if name is None or r.model == name]
            n_flushes = srv.stats(name).n_flushes
            want = LatencyStats.from_records(records, n_flushes)
            assert srv.stats(name) == want
            measured = srv.report(name)["measured"]
            assert measured["requests"] == want.n_requests
            assert measured["p99_ms"] == round(want.p99_ms, 3)
            assert measured["mean_ms"] == round(want.mean_ms, 3)
            assert measured["samples_per_s"] == round(want.samples_per_s, 1)
        srv.reset_stats()
        assert srv.stats().n_requests == 0 and srv.stats("m").n_requests == 0
