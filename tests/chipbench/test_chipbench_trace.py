"""The trace reduction on a synthetic event list and on a trimmed trace
recorded on a TPU v5e."""

import json
from pathlib import Path

import pytest

from chipbench import trace_reduce as tr

FIXTURE = Path(tr.__file__).resolve().parent / "fixtures" / "paper_offline_trace.json"


def _synthetic() -> tr.Trace:
    # window [0, 100) ns; ops overlap on plane 0; plane 1 idles longer
    return tr.Trace(
        device={
            "/device:TPU:0": [("fusion.1", 10, 30), ("cam_match_pallas", 20, 50),
                              ("copy.2", 72, 80), ("early", -20, 5)],
            "/device:TPU:1": [("cam_match_pallas", 0, 40)],
        },
        host=[("chipbench.window", 0, 100), ("chipbench.score_file", 0, 55),
              ("chipbench.drain", 55, 75), ("chipbench.score_file", 76, 100)],
    )


def test_synthetic_busy_idle_kernel_and_breakdown():
    r = tr.reduce(_synthetic())
    # plane 0 busy [0,5) [10,50) [72,80) = 53; plane 1 busy [0,40) = 40
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx((53 + 40) / 2 * 1e-9)
    assert r.n_devices == 2
    assert r.idle_pct == pytest.approx(100 * (1 - 46.5 / 100))
    secs, calls = tr.kernel_time(_synthetic(), "cam_match")
    assert calls == 2 and secs == pytest.approx((30 + 40) * 1e-9)
    ops = dict(r.device_ops)
    assert ops["cam_match_pallas"] == pytest.approx(70e-9)
    assert ops["early"] == pytest.approx(5e-9)  # clipped to the window
    # idle gaps: plane 0 [5,10) at 7.5 -> score_file, [50,72) at 61 -> drain,
    # [80,100) at 90 -> score_file; plane 1 [40,100) at 70 -> drain (the
    # latest started); averaged over the two planes
    gaps = dict(r.idle_gaps)
    assert gaps["chipbench.drain"] == pytest.approx((22 + 60) / 2 * 1e-9)
    assert gaps["chipbench.score_file"] == pytest.approx((5 + 20) / 2 * 1e-9)


def test_trace_without_window_is_an_error():
    t = _synthetic()
    t.host = [x for x in t.host if x[0] != "chipbench.window"]
    with pytest.raises(ValueError, match="chipbench.window"):
        tr.reduce(t)


def _brute_busy(events, w0, w1):
    """Busy time by walking sorted endpoints: an independent union."""
    points = sorted({w0, w1, *(max(min(x, w1), w0) for _, s, e in events for x in (s, e))})
    busy = 0.0
    for a, b in zip(points, points[1:]):
        mid = (a + b) / 2
        if any(s <= mid < e for _, s, e in events):
            busy += b - a
    return busy


def test_chip_trace_reduces_to_known_numbers():
    trace = tr.Trace.from_json(json.loads(FIXTURE.read_text()))
    r = tr.reduce(trace)
    w0, w1 = tr.window_of(trace)
    want = sum(_brute_busy(ev, w0, w1) for ev in trace.device.values()) / len(trace.device)
    assert r.busy_s == pytest.approx(want * 1e-9, rel=1e-9)
    assert r.window_s == pytest.approx((w1 - w0) * 1e-9)
    secs, calls = tr.kernel_time(trace, "cam_match")
    idle = sum(s for _, s in r.idle_gaps)
    assert idle == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
    # three 8,192-row calls of the paper-scale kernel, as read by hand
    assert calls == 3 and secs == pytest.approx(7.471741196, rel=1e-9)
    assert r.busy_s == pytest.approx(7.474589515, rel=1e-9)
    assert r.window_s == pytest.approx(7.489583895, rel=1e-9)
    assert r.device_ops[0][0] == "cam_match_pallas.1"
    assert r.idle_gaps == [("chipbench.score_file", pytest.approx(0.01499438, rel=1e-6))]


@pytest.mark.parametrize("suffix", ["offline", "offline_floats"])
def test_offline_readers_on_the_chip_trace(suffix):
    """The per-layer readers of an offline cell, fed the recorded trace of
    three 8,192-row paper-scale calls."""
    from chipbench import harness, spec, work

    trace = tr.Trace.from_json(json.loads(FIXTURE.read_text()))
    sizes = work.ModelSizes(leaves=1_048_576, features=130, outputs=8, n_bins=256)
    peaks = work.peaks_for("TPU v5 lite")
    reduction = tr.reduce(trace)
    outcome = harness.Outcome(metrics={}, attempted=3 * 8192, failed=0, rows_done=3 * 8192,
                              window_s=reduction.window_s, kernel_call_rows=[8192] * 3)
    rec = harness.RunRecord(cell=None, sizes=sizes, peaks=peaks, chips=1, outcome=outcome,
                            counters={}, trace=trace, reduction=reduction)
    read = {name: spec.metric_reader(f"{name}.{suffix}").read(rec)
            for name in ("cam_match_roofline", "mfu", "device_idle")}
    least = 3 * 8192 * sizes.ops_per_row / peaks["int8_ops_per_s"]
    assert read["cam_match_roofline"] == pytest.approx(100 * least / 7.471741196, rel=1e-9)
    assert read["mfu"] == pytest.approx(100 * least / reduction.window_s, rel=1e-9)
    assert 0 < read["mfu"] < read["cam_match_roofline"] < 100
    assert read["device_idle"] == pytest.approx(100 * (1 - 7.474589515 / 7.489583895), rel=1e-6)
    # a call the trace does not hold leaves the kernel's share unread
    outcome.kernel_call_rows.append(8192)
    assert spec.metric_reader(f"cam_match_roofline.{suffix}").read(rec) is None


def test_online_readers_on_a_synthetic_trace():
    from chipbench import harness, spec

    reduction = tr.reduce(_synthetic())
    outcome = harness.Outcome(metrics={}, attempted=20, failed=0, rows_done=960, window_s=1e-7)
    rec = harness.RunRecord(cell=None, sizes=None, peaks=None, chips=1, outcome=outcome,
                            counters={"served_rows": 960, "flushes": 10},
                            trace=_synthetic(), reduction=reduction)
    read = lambda name: spec.metric_reader(name).read(rec)  # noqa: E731
    assert read("rows_per_flush.online") == 96
    assert read("device_ms_per_flush.online") == pytest.approx(1e3 * reduction.busy_s / 10)
    assert read("device_idle.online") == pytest.approx(reduction.idle_pct)
    rec.counters = {"served_rows": 0, "flushes": 0}  # no flush in the window: nothing to read
    assert read("rows_per_flush.online") is None and read("device_ms_per_flush.online") is None
