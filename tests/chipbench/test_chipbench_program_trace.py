"""The program's spans in a trace (``program_trace``): idle charged to the
innermost span of either prefix, the readers of program spans and
counters on hand-built records and on a trimmed v5e trace, and a real
profiler file read back."""

import json
from pathlib import Path

import pytest

from chipbench import harness, program_trace as pt, spec, trace_reduce as tr

FIXTURES = Path(tr.__file__).resolve().parent / "fixtures"
MS = 1_000_000  # ns


def _offline() -> pt.ProgramTrace:
    """Two chunks in a 100 ms window; the device runs [30, 50) and [70, 90) ms."""
    host = [("chipbench.window", 0, 100 * MS), ("chipbench.score_file", 0, 100 * MS),
            ("xtime.score.bind", 0, 5 * MS)]
    for name, s, e in [("read", 5, 10), ("bin", 10, 20), ("pad", 20, 28), ("dispatch", 28, 30),
                       ("read", 30, 40), ("bin", 40, 60), ("pad", 60, 68), ("dispatch", 68, 70),
                       ("wait", 70, 71), ("fetch", 71, 72), ("write", 72, 74), ("read", 74, 75),
                       ("wait", 75, 90), ("fetch", 90, 93), ("write", 93, 94)]:
        host.append((f"xtime.score.{name}", s * MS, e * MS))
    return pt.ProgramTrace(device={"/device:TPU:0": [("cam_match_pallas.1", 30 * MS, 50 * MS),
                                                     ("cam_match_pallas.1", 70 * MS, 90 * MS)]},
                           host=host)


def _record(trace):
    outcome = harness.Outcome(metrics={}, attempted=1, failed=0, rows_done=1, window_s=0.1)
    return harness.RunRecord(cell=None, sizes=None, peaks=None, chips=1, outcome=outcome,
                             counters={}, trace=trace,
                             reduction=None if trace is None else tr.reduce(trace))


def test_idle_goes_to_the_innermost_program_span():
    r = pt.breakdown(_offline())
    assert r.busy_s == pytest.approx(0.040)
    gaps = dict(r.idle_gaps)
    # [0,30) at 15 -> bin; [50,70) at 60 -> pad (started at 60, open at 60);
    # [90,100) at 95 -> score_file (write ended at 94)
    assert gaps == {"xtime.score.bin": pytest.approx(0.030),
                    "xtime.score.pad": pytest.approx(0.020),
                    "chipbench.score_file": pytest.approx(0.010)}
    # the benchmark's own reduction sees the benchmark's spans alone
    plain = tr.Trace(_offline().device, [x for x in _offline().host
                                         if x[0].startswith("chipbench.")])
    assert dict(tr.reduce(plain).idle_gaps) == {"chipbench.score_file": pytest.approx(0.060)}


def test_paper_fixture_reads_as_before():
    """A trace with no program span reduces exactly as ``trace_reduce``
    reduces it, through the JSON round trip."""
    d = json.loads((FIXTURES / "paper_offline_trace.json").read_text())
    trace = pt.ProgramTrace.from_json(d)
    assert trace.meta == [{}] * len(trace.host)
    assert pt.breakdown(trace) == tr.reduce(tr.Trace.from_json(d))
    assert pt.ProgramTrace.from_json(json.loads(json.dumps(trace.to_json()))) == trace
    assert pt.stages(trace) == []


def test_host_ms_per_chunk_on_a_hand_built_record():
    read = spec.metric_reader("host_ms_per_chunk.offline_floats").read
    # read 5+10+1, bin 10+20, pad 8+8, dispatch 2+2, fetch 1+3, write 2+1
    # (the waits left out) over two dispatched chunks
    assert read(_record(_offline())) == pytest.approx((16 + 30 + 16 + 4 + 4 + 3) / 2)
    bare = _offline()
    bare.host = [x for x in bare.host if not x[0].startswith("xtime.")]
    bare.meta = []
    assert read(_record(pt.ProgramTrace(bare.device, bare.host))) is None
    assert read(_record(None)) is None


def _online() -> pt.ProgramTrace:
    """Two replicas, three flushes each, in a 100 ms window; a flush at
    -5 ms starts before the window and is not counted."""
    host, meta = [("chipbench.window", 0, 100 * MS)], [{}]

    def flush(rid, s, e, wait, routed, queue_s, started, inbox_s):
        host.append(("xtime.serve.flush", s * MS, e * MS))
        meta.append({"model": "gas", "replica": rid, "seq": 0, "rows": 9,
                     "routed_requests": routed, "queue_wait_s": queue_s,
                     "started_requests": started, "inbox_wait_s": inbox_s})
        host.append(("xtime.serve.wait", (e - wait) * MS, e * MS))
        meta.append({})

    flush(0, -5, -3, 1, 0, 0.0, 0, 0.0)
    flush(0, 10, 14, 2, 40, 0.5, 20, 0.002)
    flush(1, 20, 23, 1, 60, 0.7, 20, 0.004)
    flush(0, 40, 44, 3, 80, 0.9, 40, 0.006)
    flush(1, 50, 52, 1, 100, 1.1, 40, 0.010)
    flush(1, 70, 73, 2, 130, 1.5, 70, 0.014)
    flush(0, 90, 96, 3, 150, 1.6, 50, 0.007)
    return pt.ProgramTrace(device={"/device:TPU:0": [("cam_match_pallas.1", 12 * MS, 13 * MS)]},
                           host=host, meta=meta)


def test_online_readers_on_a_hand_built_record():
    rec = _record(_online())
    read = lambda name: spec.metric_reader(name).read(rec)  # noqa: E731
    # model "gas": routed 40 -> 150 over the flushes in the window, 0.5 -> 1.6 s
    assert read("queue_wait_ms.online") == pytest.approx(1e3 * 1.1 / 110)
    # replica 0: 20 -> 50 started, 0.002 -> 0.007 s; replica 1: 20 -> 70, 0.004 -> 0.014 s
    assert read("inbox_wait_ms.online") == pytest.approx(1e3 * (0.005 + 0.010) / (30 + 50))
    # flushes inside the window (not the one before it): 4+3+4+2+3+6 = 22 ms,
    # less waits 2+1+3+1+2+3 = 12 ms, over six flushes
    assert read("host_ms_per_flush.online") == pytest.approx((22 - 12) / 6)


def test_online_readers_with_nothing_to_read():
    one = _online()
    keep = [i for i, (n, s, _) in enumerate(one.host)
            if n == "chipbench.window" or s < 15 * MS]
    single = pt.ProgramTrace(one.device, [one.host[i] for i in keep], [one.meta[i] for i in keep])
    for name in ("queue_wait_ms.online", "inbox_wait_ms.online"):
        # one flush in the window: no change to read
        assert spec.metric_reader(name).read(_record(single)) is None
        assert spec.metric_reader(name).read(_record(None)) is None
    empty = pt.ProgramTrace(one.device, [one.host[0]])
    for name in ("queue_wait_ms.online", "inbox_wait_ms.online", "host_ms_per_flush.online"):
        assert spec.metric_reader(name).read(_record(empty)) is None


def test_longest_spans():
    trace = pt.ProgramTrace(device={}, host=[
        ("chipbench.window", 100 * MS, 1000 * MS), ("xtime.gc", 150 * MS, 160 * MS),
        ("xtime.gc", 300 * MS, 340 * MS), ("xtime.gc", 50 * MS, 130 * MS),
        ("xtime.serve.flush", 400 * MS, 900 * MS)])
    # the pass that began before the window counts its 30 ms inside it
    assert pt.longest(trace, "xtime.gc", top=2) == [(pytest.approx(0.2), pytest.approx(40)),
                                                     (pytest.approx(0.0), pytest.approx(30))]
    assert pt.longest(trace, "xtime.score.bin") == []


def test_gas_offline_chip_trace_reduces_to_known_numbers():
    """A trimmed gas-offline trace from a v5e (nine chunks of 8,192 float
    rows): the host's binning and padding take all of the device's idle
    time, and the readers read it as by hand."""
    d = json.loads((FIXTURES / "gas_offline_trace.json").read_text())
    trace = pt.ProgramTrace.from_json(d)
    r = pt.breakdown(trace)
    assert r.window_s == pytest.approx(0.707769797, rel=1e-9)
    assert r.busy_s == pytest.approx(0.447749163, rel=1e-9)
    assert tr.kernel_time(trace, "cam_match") == (pytest.approx(0.447039387, rel=1e-9), 9)
    assert r.idle_gaps == [("xtime.score.bin", pytest.approx(0.236208332, rel=1e-9)),
                           ("xtime.score.pad", pytest.approx(0.023812302, rel=1e-9))]
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(r.window_s - r.busy_s, rel=1e-9)
    stages = {n: (c, s) for n, c, s in pt.stages(trace)}
    assert stages["xtime.score.bin"] == (10, pytest.approx(0.655486354, rel=1e-9))
    assert stages["xtime.score.dispatch"][0] == 9
    read = spec.metric_reader("host_ms_per_chunk.offline_floats").read
    assert read(_record(trace)) == pytest.approx(77.952418556, rel=1e-9)
    # the device's idle share, read by the accepted reader, as before
    idle = spec.metric_reader("device_idle.offline_floats").read(_record(trace))
    assert idle == pytest.approx(100 * (1 - 0.447749163 / 0.707769797), rel=1e-9)


def test_a_profiler_file_read_back(tmp_path):
    """Spans of both prefixes and their metadata, read from a real
    profiler file found by the record's cell, and only when its window
    is the record's."""
    import jax
    from jax.profiler import TraceAnnotation

    out = tmp_path / "trace" / "cell"
    jax.profiler.start_trace(str(out))
    with TraceAnnotation("chipbench.window"):
        with TraceAnnotation("xtime.serve.flush", model="m", replica=1, queue_wait_s=0.25):
            pass
        with TraceAnnotation("other.span"):
            pass
    jax.profiler.stop_trace()
    (path,) = out.glob("plugins/profile/*/*.xplane.pb")
    trace = pt.read_xplane(path)
    names = [n for n, _, _ in trace.host]
    assert sorted(names) == ["chipbench.window", "xtime.serve.flush"]
    assert trace.meta[names.index("xtime.serve.flush")] == {
        "model": "m", "replica": 1, "queue_wait_s": 0.25}
    rec = _record(None)
    rec.trace = tr.read_xplane(path)  # what the harness reads
    rec.cell = spec.Cell(name="cell", chips=1, config={}, config_path=path, traffic={},
                         traffic_path=path)
    assert pt.of_run(rec, cache=tmp_path) == trace
    rec.trace = tr.Trace({}, [("chipbench.window", 0, 1)])  # another run's window
    assert pt.of_run(rec, cache=tmp_path) is None
    rec.cell = spec.Cell(name="none", chips=1, config={}, config_path=path, traffic={},
                         traffic_path=path)
    assert pt.of_run(rec, cache=tmp_path) is None
