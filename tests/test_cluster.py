"""Async serving tier: bit-equality vs the synchronous ServeLoop oracle,
heartbeat-timeout failover, crash failover, straggler exclusion, hot-swap
under live traffic, overload shedding, elastic restore, adaptive flush
windows, deterministic traffic replay, and thread-safety of the shared
MicroBatcher/TableRegistry (DESIGN.md §12)."""

import dataclasses
import threading
import time

import jax
import numpy as np
import pytest

from repro.api import build
from repro.core.quantize import FeatureQuantizer
from repro.core.trees import GBDTParams, train_gbdt
from repro.data.tabular import make_dataset
from repro.ft.runtime import StragglerMonitor
from repro.serve import (
    AdaptiveWindow,
    ClusterClosed,
    ClusterServer,
    MicroBatcher,
    ServeLoop,
    ShedError,
    TableRegistry,
    make_trace,
    replay_trace,
)

pytestmark = pytest.mark.filterwarnings(
    "ignore:pallas TPU support unavailable"
)


@pytest.fixture(scope="module")
def served():
    """(artifact_v1, artifact_v2, xb_test) — v1/v2 differ somewhere."""
    ds = make_dataset("churn")
    q = FeatureQuantizer.fit(ds.x_train, 256)
    xb_tr = q.transform(ds.x_train)
    ens_a = train_gbdt(
        xb_tr, ds.y_train, task="binary", n_bins=256,
        params=GBDTParams(n_rounds=4, max_leaves=16),
    )
    ens_b = train_gbdt(
        xb_tr, ds.y_train, task="binary", n_bins=256,
        params=GBDTParams(n_rounds=2, max_leaves=8),
    )
    xb = q.transform(ds.x_test).astype(np.int32)[:256]
    return build(ens_a), build(ens_b), xb


def _server(**kw):
    defaults = dict(
        n_replicas=2, flush_rows=16, max_batch=128, heartbeat_timeout_s=0.6,
        monitor_interval_s=0.02,
    )
    defaults.update(kw)
    return ClusterServer(**defaults)


def _oracle_results(artifact, trace, xb, *, flush_rows=16):
    """Replay the identical trace through the synchronous ServeLoop."""
    reg = TableRegistry()
    reg.register("m", artifact)
    loop = ServeLoop(reg, window_s=100.0, flush_rows=flush_rows, max_batch=128)
    res = replay_trace(loop.submit, trace, {"m": xb}, speed=0)
    loop.drain()
    return [loop.result(h) for h in res.handles]


# -- adaptive window ----------------------------------------------------------


def test_adaptive_window_tracks_arrival_rate():
    w = AdaptiveWindow(min_s=1e-3, max_s=0.1, target_rows=10, alpha=0.5)
    assert w.window_s == 0.1  # no observations yet: maximum coalescing wait
    t = 0.0
    for _ in range(20):  # 1 row per ms -> window ~ 10 rows * 1ms = 10ms
        w.observe(t)
        t += 1e-3
    assert 5e-3 < w.window_s < 2e-2
    for _ in range(20):  # traffic goes quiet: window grows to the cap
        w.observe(t)
        t += 10.0
    assert w.window_s == 0.1
    for _ in range(30):  # flood: window floors at min_s
        w.observe(t)
        t += 1e-6
    assert w.window_s == 1e-3


def test_adaptive_window_multirow_counts_rows():
    w = AdaptiveWindow(min_s=1e-4, max_s=1.0, target_rows=100, alpha=1.0)
    w.observe(0.0, n_rows=1)
    w.observe(1e-2, n_rows=10)  # 10 rows in 10ms -> 1ms/row -> 100ms window
    assert w.window_s == pytest.approx(0.1)


# -- straggler monitor (EWMA mode) -------------------------------------------


def test_straggler_ewma_flags_and_freezes_baseline():
    mon = StragglerMonitor(threshold=3.0, ewma_alpha=0.5, min_samples=4)
    for s in range(6):
        assert not mon.record(s, 0.01)
    assert mon.baseline == pytest.approx(0.01)
    # flagged samples must NOT be folded into the baseline — a replica
    # that turns slow keeps getting flagged instead of normalizing
    for s in range(6, 10):
        assert mon.record(s, 1.0)
    assert mon.baseline == pytest.approx(0.01)
    assert len(mon.events) == 4 and "baseline" in mon.events[0]


def test_straggler_median_mode_unchanged():
    mon = StragglerMonitor(threshold=3.0)
    for s in range(10):
        assert not mon.record(s, 0.1)
    assert mon.record(10, 1.0)
    assert mon.events[0]["median"] == pytest.approx(0.1)


# -- traffic generation -------------------------------------------------------


def test_trace_deterministic_and_heavy_tailed():
    a = make_trace(["x", "y"], 500, seed=11, mean_interval_s=1e-3)
    b = make_trace(["x", "y"], 500, seed=11, mean_interval_s=1e-3)
    assert a == b  # same seed, same bits
    c = make_trace(["x", "y"], 500, seed=12, mean_interval_s=1e-3)
    assert a != c
    gaps = np.diff([0.0] + [r.t for r in a.requests])
    assert gaps.max() > 5 * gaps.mean()  # heavy tail: bursts + long quiets
    # zipf popularity: the first-listed model is the hottest
    n_x = sum(r.model == "x" for r in a.requests)
    assert n_x > len(a.requests) // 2
    assert all(r.n_rows >= 1 for r in a.requests)


def test_trace_marks_and_stream_wrap():
    tr = make_trace(
        {"m": 10}, 50, seed=0, marks=[(0.5, "kill"), (0.0, "start")],
    )
    assert {m.name for m in tr.marks} == {"kill", "start"}
    assert all(0 <= r.row_start < 10 for r in tr.requests)
    assert tr.horizon_s >= tr.marks[0].t
    merged = tr.merged()
    assert len(merged) == 52
    assert all(
        merged[i].t <= merged[i + 1].t for i in range(len(merged) - 1)
    )


def test_replay_paces_and_fires_marks():
    # fake clock: sleep() advances time instantly -> submits land exactly
    # on the (speed-warped) schedule
    t = [0.0]
    trace = make_trace(["m"], 20, seed=3, mean_interval_s=1e-2,
                       marks=[(0.5, "mid")])
    seen = []
    fired = []
    res = replay_trace(
        lambda model, q: seen.append((t[0], q.shape[0])) or len(seen),
        trace, {"m": np.zeros((8, 4), np.int32)},
        speed=2.0,
        callbacks={"mid": lambda: fired.append(t[0])},
        clock=lambda: t[0],
        sleep=lambda d: t.__setitem__(0, t[0] + d),
    )
    assert res.submitted == 20 and res.shed == 0
    for (at, _), req in zip(seen, trace.requests):
        assert at == pytest.approx(req.t / 2.0)
    assert len(fired) == 1
    assert fired[0] == pytest.approx(trace.marks[0].t / 2.0)


# -- bit-equality vs the synchronous oracle -----------------------------------


def test_cluster_bit_equal_to_sync_loop(served):
    art, _, xb = served
    trace = make_trace(["m"], 120, seed=5, mean_interval_s=2e-4, mean_rows=1.5)
    oracle = _oracle_results(art, trace, xb)
    with _server() as srv:
        srv.register("m", art)
        res = replay_trace(srv.submit, trace, {"m": xb}, speed=0)
        srv.drain(timeout=60)
        stats = srv.stats("m")
        assert stats.n_requests == 120
        assert stats.n_rows == trace.n_rows
        assert stats.p99_ms >= stats.p50_ms >= 0.0
        for h, want in zip(res.handles, oracle):
            np.testing.assert_array_equal(h.result(5), want)


def test_cluster_margin_kind_close_to_oracle(served):
    art, _, xb = served
    trace = make_trace(["m"], 40, seed=6, mean_interval_s=2e-4)
    with _server(kind="margin") as srv:
        srv.register("m", art)
        res = replay_trace(srv.submit, trace, {"m": xb}, speed=0)
        srv.drain(timeout=60)
        eng = art.engine()
        for h, req in zip(res.handles, trace.requests):
            rows = np.take(
                xb, np.arange(req.row_start, req.row_start + req.n_rows),
                axis=0, mode="wrap",
            )
            # bucket shape changes XLA accumulation order (same tolerance
            # as the sync serving tests)
            np.testing.assert_allclose(
                h.result(5), np.asarray(eng.raw_margin(rows)),
                rtol=1e-5, atol=1e-6,
            )


# -- failure modes ------------------------------------------------------------


def test_heartbeat_timeout_failover_preserves_bits(served):
    art, _, xb = served
    trace = make_trace(["m"], 100, seed=8, mean_interval_s=2e-4)
    oracle = _oracle_results(art, trace, xb)
    with _server() as srv:
        srv.register("m", art)
        # warm both replicas, then go silent on one mid-traffic
        warm = replay_trace(srv.submit, trace, {"m": xb}, speed=0)
        srv.drain(timeout=60)
        srv.inject_hang(0)
        res = replay_trace(srv.submit, trace, {"m": xb}, speed=0)
        srv.drain(timeout=60)  # monitor must declare death + re-route
        rep = srv.report()
        assert rep["failovers"] >= 1
        assert rep["replicas"][0]["state"] == "dead"
        for h, want in zip(warm.handles, oracle):
            np.testing.assert_array_equal(h.result(5), want)
        for h, want in zip(res.handles, oracle):
            np.testing.assert_array_equal(h.result(5), want)


def test_crash_failover_mid_traffic(served):
    art, _, xb = served
    trace = make_trace(["m"], 100, seed=9, mean_interval_s=2e-4)
    oracle = _oracle_results(art, trace, xb)
    with _server() as srv:
        srv.register("m", art)
        srv.inject_crash(0)  # fail-stop on its first routed job
        res = replay_trace(srv.submit, trace, {"m": xb}, speed=0)
        srv.drain(timeout=60)
        rep = srv.report()
        assert rep["replicas"][0]["state"] == "dead"
        assert rep["failovers"] >= 1
        assert rep["replicas"][1]["served_requests"] == 100
        for h, want in zip(res.handles, oracle):
            np.testing.assert_array_equal(h.result(5), want)


def test_straggler_excluded_from_routing(served):
    art, _, xb = served
    # heartbeat_timeout_s must exceed worst-case flush time (workers beat
    # BETWEEN jobs): a 1s injected delay under a 0.6s timeout reads as
    # death, not straggling (DESIGN.md §12)
    with _server(
        straggler_threshold=3.0, straggler_strikes=2,
        heartbeat_timeout_s=10.0,
    ) as srv:
        srv.register("m", art)
        # warmup: enough flushes to pull the shared EWMA baseline down to
        # steady-state flush time (first flushes pay jit compiles)
        for _ in range(12):
            hs = [srv.submit("m", xb[i]) for i in range(16)]
            srv.drain(timeout=60)
            for h in hs:
                h.result(5)
        srv.inject_delay(0, 1.0)
        handles = []
        for _ in range(6):  # alternating routing feeds the slow replica
            hs = [srv.submit("m", xb[i]) for i in range(16)]
            srv.drain(timeout=60)
            handles.extend(hs)
        rep = srv.report()
        assert rep["replicas"][0]["state"] == "excluded"
        assert rep["straggler_events"] >= 2
        direct = np.asarray(art.engine().predict(xb[:16]))
        for i, h in enumerate(handles):  # slow != wrong
            j = i % 16
            np.testing.assert_array_equal(h.result(5), direct[j : j + 1])
        # excluded replica no longer receives new work
        before = srv.report()["replicas"][0]["flushes"]
        for i in range(16):
            srv.submit("m", xb[i])
        srv.drain(timeout=60)
        assert srv.report()["replicas"][0]["flushes"] == before


def test_elastic_restore_rejoins_rotation(served):
    art, _, xb = served
    with _server() as srv:
        srv.register("m", art)
        srv.kill_replica(0)
        assert srv.report()["replicas"][0]["state"] == "dead"
        hs = [srv.submit("m", xb[i]) for i in range(32)]
        srv.drain(timeout=60)
        with pytest.raises(ValueError):
            srv.restore_replica(1)  # still alive
        srv.restore_replica(0)
        hs2 = [srv.submit("m", xb[i]) for i in range(32)]
        srv.drain(timeout=60)
        rep = srv.report()
        assert rep["replicas"][0]["state"] == "alive"
        direct = np.asarray(art.engine().predict(xb[:32]))
        for i, h in enumerate([*hs, *hs2]):
            j = i % 32
            np.testing.assert_array_equal(h.result(5), direct[j : j + 1])


def test_hot_swap_under_live_traffic(served):
    art_a, art_b, xb = served
    pred_a = np.asarray(art_a.engine().predict(xb))
    pred_b = np.asarray(art_b.engine().predict(xb))
    assert (pred_a != pred_b).any()  # the swap must be observable
    with _server() as srv:
        srv.register("m", art_a)
        pre = [srv.submit("m", xb[i]) for i in range(48)]
        srv.register("m", art_b)  # hot swap on every replica, mid-traffic
        post = [srv.submit("m", xb[i]) for i in range(48)]
        srv.drain(timeout=60)
        # in-flight-at-swap requests are served by exactly one of the two
        # versions, never a torn mix
        for i, h in enumerate(pre):
            got = h.result(5)
            assert (
                np.array_equal(got, pred_a[i : i + 1])
                or np.array_equal(got, pred_b[i : i + 1])
            )
        # post-swap requests always see the new version
        for i, h in enumerate(post):
            np.testing.assert_array_equal(h.result(5), pred_b[i : i + 1])


def test_register_compiles_every_serving_bucket(served):
    """Cold buckets compile in register, never in a flush: workers beat
    between jobs, so a flush that compiled would go as silent as a hang
    (or, at a few ms, read as a straggler)."""
    art, _, xb = served
    art = dataclasses.replace(art)  # fresh engine cache: nothing compiled
    compiles = []

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    with _server() as srv:
        entry = srv.register("m", art)
        program = entry.engine._jitted("predict", donate=True)
        sizes = MicroBatcher.for_engine(entry.engine, max_batch=128).bucket.sizes()
        assert program._cache_size() == len(sizes)
        handles = []
        jax.monitoring.register_event_duration_secs_listener(on_event)
        try:
            # every bucket, each reached by several request counts
            for n in [1, 3, *(s + 1 for s in sizes[:-1]), 100, 127]:
                handles.append((n, srv.submit("m", xb[:n])))
                srv.drain(timeout=60)
        finally:
            jax.monitoring.unregister_event_duration_listener(on_event)
        assert compiles == []
        assert srv.report()["failovers"] == 0
        direct = np.asarray(art.engine().predict(xb))
        for n, h in handles:
            np.testing.assert_array_equal(h.result(5), direct[:n])


def test_registry_warmup_runs_before_install(served):
    """A hot swap's warmup sees the new engine while lookups still
    resolve the old entry; the new one is installed only afterwards."""
    art_a, art_b, _ = served
    reg = TableRegistry()
    reg.register("m", art_a)
    seen = []

    def warmup(engine):
        live = reg.get("m")
        seen.append((live.artifact is art_a, live.version, engine is live.engine))

    entry = reg.register("m", art_b, warmup=warmup)
    assert seen == [(True, 1, False)]
    assert reg.version("m") == 2 and reg.engine("m") is entry.engine


# -- admission control --------------------------------------------------------


def test_overload_sheds_with_explicit_backpressure(served):
    art, _, xb = served
    with _server(
        flush_rows=1000, max_queue_rows=8,
        window=AdaptiveWindow(min_s=5.0, max_s=5.0),
    ) as srv:
        srv.register("m", art)
        handles, sheds = [], 0
        for i in range(12):  # queue bound is 8 rows -> 4 sheds
            try:
                handles.append(srv.submit("m", xb[i]))
            except ShedError:
                sheds += 1
        assert sheds == 4 and len(handles) == 8
        assert srv.report()["shed"] == {"m": 4}
        srv.drain(timeout=60)  # accepted requests still complete correctly
        direct = np.asarray(art.engine().predict(xb[:8]))
        for i, h in enumerate(handles):
            np.testing.assert_array_equal(h.result(5), direct[i : i + 1])


def test_submit_errors(served):
    art, _, xb = served
    srv = _server(n_replicas=1)
    srv.register("m", art)
    with pytest.raises(KeyError):
        srv.submit("ghost", xb[0])
    with pytest.raises(ValueError):
        srv.submit("m", np.zeros((0, xb.shape[1]), np.int32))
    srv.close()
    with pytest.raises(ClusterClosed):
        srv.submit("m", xb[0])
    srv.close()  # idempotent


# -- thread safety of the shared serving primitives ---------------------------


def test_microbatcher_concurrent_submit_flush(served):
    art, _, xb = served
    eng = art.engine()
    mb = MicroBatcher.for_engine(eng, max_batch=128)
    direct = np.asarray(eng.predict(xb))
    results: dict[int, np.ndarray] = {}
    res_lock = threading.Lock()
    rid_row: dict[int, int] = {}
    stop = threading.Event()

    def submitter(rows):
        for i in rows:
            rid = mb.submit(xb[i])
            with res_lock:
                rid_row[rid] = i
            time.sleep(0)

    def flusher():
        while not stop.is_set() or mb.pending_requests:
            out = mb.flush()
            with res_lock:
                results.update(out)

    threads = [
        threading.Thread(target=submitter, args=(range(k, 96, 4),))
        for k in range(4)
    ]
    fl = threading.Thread(target=flusher)
    fl.start()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    stop.set()
    fl.join()
    assert len(results) == 96  # nothing lost, nothing double-flushed
    for rid, row in rid_row.items():
        np.testing.assert_array_equal(results[rid], direct[row : row + 1])


def test_registry_concurrent_swap_and_lookup(served):
    art_a, art_b, xb = served
    reg = TableRegistry()
    reg.register("m", art_a)
    errors: list[BaseException] = []

    def swapper(artifact):
        try:
            for _ in range(10):
                reg.register("m", artifact)
        except BaseException as exc:  # noqa: BLE001
            errors.append(exc)

    def reader():
        try:
            for _ in range(50):
                entry = reg.get("m")
                # a reader sees a whole entry, never a torn one
                assert entry.engine is not None and entry.version >= 1
                assert reg.version("m") >= 1
        except BaseException as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [
        threading.Thread(target=swapper, args=(art_a,)),
        threading.Thread(target=swapper, args=(art_b,)),
        threading.Thread(target=reader),
        threading.Thread(target=reader),
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors
    assert reg.version("m") == 21  # 1 + 2 swappers x 10, no lost updates
