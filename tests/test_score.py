"""Streaming offline scoring (repro.score, DESIGN.md §14).

The load-bearing contract is bit-equivalence: for any chunking — sizes
that don't divide the row count, 1-row tails, double-buffering on or
off, single device or the 8-fake-device mesh under the ``batch`` NoC
program — the concatenated streamed outputs must be BIT-IDENTICAL to a
one-shot engine call over the whole file.  Plus the golden loop: the
committed ``xgb_deep`` fixture goes ingest -> build -> save -> score
(from the committed ``.npy``) -> verify against the frozen record.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st
from oracles import run_on_8_devices

import repro
from repro.api import build
from repro.core.quantize import FeatureQuantizer
from repro.core.trees import random_deep_ensemble
from repro.score import (
    NpySource,
    PredictionWriter,
    ScoreResult,
    open_columnar,
    score_file,
)

FIXTURES = Path(__file__).resolve().parent / "fixtures"
SCRIPTS = Path(__file__).resolve().parent.parent / "scripts"


@pytest.fixture(scope="module")
def binary_cm():
    """Small gridless binary model + pre-binned int queries + oracle."""
    ens = random_deep_ensemble(n_trees=12, depth=4, n_features=9,
                               n_bins=32, seed=3)
    cm = build(ens)
    rng = np.random.default_rng(0)
    q = rng.integers(0, 32, size=(301, 9)).astype(np.int32)
    eng = cm.engine()
    return cm, q, np.asarray(eng.raw_margin(q)), np.asarray(eng.predict(q))


@pytest.fixture(scope="module")
def multiclass_cm():
    """Multi-channel margins: the (B, n_outputs) writer/streaming path."""
    ens = random_deep_ensemble(n_trees=9, depth=3, n_features=6, n_bins=16,
                               task="multiclass", n_classes=3, seed=11)
    cm = build(ens)
    rng = np.random.default_rng(1)
    q = rng.integers(0, 16, size=(157, 6)).astype(np.int32)
    eng = cm.engine()
    return cm, q, np.asarray(eng.raw_margin(q)), np.asarray(eng.predict(q))


# -- bit-equivalence: streamed == one-shot -------------------------------------


@settings(max_examples=12, deadline=None)
@given(chunk_rows=st.integers(min_value=1, max_value=400))
def test_streamed_bit_equal_one_shot_any_chunking(binary_cm, chunk_rows):
    """THE acceptance property: for arbitrary chunk sizes — dividing the
    301 rows or not — streamed outputs are bit-identical to one-shot."""
    cm, q, ref_m, ref_p = binary_cm
    r = score_file(cm, q, kind="margin", chunk_rows=chunk_rows)
    np.testing.assert_array_equal(r.values, ref_m)
    assert r.values.dtype == ref_m.dtype
    r = score_file(cm, q, kind="predict", chunk_rows=chunk_rows)
    np.testing.assert_array_equal(r.values, ref_p)


def test_double_buffer_off_same_bits(binary_cm):
    cm, q, ref_m, _ = binary_cm
    on = score_file(cm, q, kind="margin", chunk_rows=33, double_buffer=True)
    off = score_file(cm, q, kind="margin", chunk_rows=33, double_buffer=False)
    np.testing.assert_array_equal(on.values, ref_m)
    np.testing.assert_array_equal(off.values, on.values)
    assert on.double_buffered and not off.double_buffered


def test_multichannel_margins_stream_bit_equal(multiclass_cm):
    cm, q, ref_m, ref_p = multiclass_cm
    assert ref_m.shape[1] == 3  # genuinely multi-channel
    for chunk in (13, 64, 157):
        r = score_file(cm, q, kind="margin", chunk_rows=chunk)
        np.testing.assert_array_equal(r.values, ref_m)
    r = score_file(cm, q, kind="predict", chunk_rows=50)
    np.testing.assert_array_equal(r.values, ref_p)
    assert r.values.dtype == np.int32


def test_empty_and_one_row_tails(binary_cm, multiclass_cm):
    cm, q, ref_m, ref_p = binary_cm
    r0 = score_file(cm, q[:0], kind="margin")
    assert r0.values.shape == (0, ref_m.shape[1])
    assert r0.n_chunks == 0 and r0.rows_per_s == 0.0
    mc, mq, mref, _ = multiclass_cm
    r0 = score_file(mc, mq[:0], kind="margin")
    assert r0.values.shape == (0, 3)
    r1 = score_file(cm, q[:1], kind="predict", chunk_rows=64)
    np.testing.assert_array_equal(r1.values, ref_p[:1])
    # a chunk size exactly one short of the row count: a 1-row tail chunk
    r = score_file(cm, q, kind="margin", chunk_rows=q.shape[0] - 1)
    np.testing.assert_array_equal(r.values, ref_m)
    assert r.n_chunks == 2


def test_mesh_batch_noc_bit_equal(binary_cm, tmp_path):
    """Chunks fan out across the 8-fake-device mesh under the 'batch'
    NoC program (replicated tables, no collective) — same bits."""
    cm, q, ref_m, _ = binary_cm
    cm.save(tmp_path / "art")
    np.save(tmp_path / "q.npy", q)
    res = run_on_8_devices(f"""
import json
from repro.api import CompiledModel
from repro.launch.mesh import make_host_mesh
from repro.score import score_file

r = score_file(CompiledModel.load({str(tmp_path / "art")!r}),
               {str(tmp_path / "q.npy")!r}, kind="margin", chunk_rows=40,
               mesh=make_host_mesh(8, 1))
print(json.dumps({{"values": r.values.tolist(), "engine": r.engine,
                  "bucket": r.bucket}}))
""")
    np.testing.assert_array_equal(np.asarray(res["values"], np.float32), ref_m)
    assert res["engine"]["devices"] == 8
    assert res["engine"]["noc_config"] == "batch"
    # the bucket must satisfy the mesh's batch-divisibility contract
    assert res["bucket"] % 8 == 0


def test_float_input_binned_chunkwise_bit_equal():
    """Float rows bin chunk-by-chunk with the artifact's own grid —
    identical to binning the whole file up front."""
    rng = np.random.default_rng(7)
    ens = random_deep_ensemble(n_trees=8, depth=4, n_features=5,
                               n_bins=32, seed=5)
    xf = rng.normal(size=(203, 5))
    fq = FeatureQuantizer.fit(xf, n_bins=32)
    cm = build(ens, quantizer=fq)
    ref = np.asarray(cm.engine().raw_margin(fq.transform(xf)))
    r = score_file(cm, xf, kind="margin", chunk_rows=48)
    assert r.binned
    np.testing.assert_array_equal(r.values, ref)


# -- file round trips ----------------------------------------------------------


def test_npy_in_npy_out_round_trip(binary_cm, tmp_path):
    cm, q, ref_m, _ = binary_cm
    np.save(tmp_path / "rows.npy", q)
    r = score_file(cm, tmp_path / "rows.npy", kind="margin",
                   chunk_rows=50, out=tmp_path / "preds")
    assert r.path == tmp_path / "preds.npy"  # suffix appended
    np.testing.assert_array_equal(np.load(r.path), ref_m)
    np.testing.assert_array_equal(r.values, ref_m)


def test_artifact_path_accepted(binary_cm, tmp_path):
    cm, q, ref_m, _ = binary_cm
    cm.save(tmp_path / "art")
    r = score_file(tmp_path / "art", q, kind="margin", chunk_rows=100)
    np.testing.assert_array_equal(r.values, ref_m)


def test_parquet_source_streams(binary_cm, tmp_path):
    pa = pytest.importorskip("pyarrow")
    import pyarrow.parquet as pq

    cm, q, ref_m, _ = binary_cm
    tbl = pa.table({f"f{i}": q[:, i] for i in range(q.shape[1])})
    pq.write_table(tbl, tmp_path / "rows.parquet", row_group_size=64)
    r = score_file(cm, tmp_path / "rows.parquet", kind="margin",
                   chunk_rows=37)
    np.testing.assert_array_equal(r.values, ref_m)
    # explicit column selection, same order
    r2 = score_file(cm, tmp_path / "rows.parquet", kind="margin",
                    columns=[f"f{i}" for i in range(q.shape[1])])
    np.testing.assert_array_equal(r2.values, ref_m)


# -- error surface -------------------------------------------------------------


def test_float_without_grid_is_a_clear_error(binary_cm):
    cm, q, _, _ = binary_cm  # built gridless
    with pytest.raises(ValueError, match="feature grid"):
        score_file(cm, q.astype(np.float64))


def test_feature_width_mismatch(binary_cm):
    cm, q, _, _ = binary_cm
    with pytest.raises(ValueError, match="feature columns"):
        score_file(cm, q[:, :4])


def test_bad_kind_and_chunk_rows(binary_cm):
    cm, q, _, _ = binary_cm
    with pytest.raises(ValueError, match="kind"):
        score_file(cm, q, kind="margins")
    with pytest.raises(ValueError, match="chunk_rows"):
        score_file(cm, q, chunk_rows=0)


def test_open_columnar_rejects_unknown_suffix(tmp_path):
    p = tmp_path / "rows.csv"
    p.write_text("1,2\n")
    with pytest.raises(ValueError, match="unsupported columnar input"):
        open_columnar(p)
    with pytest.raises(FileNotFoundError):
        open_columnar(tmp_path / "nope.npy")
    with pytest.raises(ValueError, match="2-D"):
        open_columnar(np.zeros(5))


def test_writer_enforces_sequential_order():
    w = PredictionWriter(10)
    w.write(0, np.zeros((4, 2), np.float32))
    with pytest.raises(ValueError, match="out-of-order"):
        w.write(8, np.zeros((2, 2), np.float32))
    w.write(4, np.zeros((6, 2), np.float32))
    out = w.finalize()
    assert out.shape == (10, 2)
    with pytest.raises(ValueError, match="overruns"):
        PredictionWriter(2).write(0, np.zeros((3,), np.float32))


def test_npy_source_is_memory_mapped(tmp_path):
    q = np.arange(20, dtype=np.int32).reshape(10, 2)
    np.save(tmp_path / "r.npy", q)
    src = open_columnar(tmp_path / "r.npy")
    assert isinstance(src, NpySource)
    assert isinstance(src.array, np.memmap)
    chunks = list(src.iter_chunks(4))
    assert [s for s, _ in chunks] == [0, 4, 8]
    # chunks are real copies: safe to donate after the source closes
    assert not any(isinstance(c, np.memmap) for _, c in chunks)
    np.testing.assert_array_equal(np.concatenate([c for _, c in chunks]), q)
    src.close()


# -- the golden loop on the committed fixture ----------------------------------


def test_xgb_deep_golden_save_score_verify(tmp_path):
    """ingest -> build -> save -> score the committed .npy on the 8-fake
    device mesh -> bit-identical to the frozen record."""
    exp = json.loads(
        (FIXTURES / "ingest" / "xgb_deep.expected.json").read_text()
    )
    res = run_on_8_devices(f"""
import json
from repro.api import CompiledModel, build
from repro.launch.mesh import make_host_mesh
from repro.score import score_file

build({str(FIXTURES / "ingest" / "xgb_deep.json")!r}).save({str(tmp_path / "art")!r})
loaded = CompiledModel.load({str(tmp_path / "art")!r})
mesh = make_host_mesh(8, 1)
rows = {str(FIXTURES / "score" / "xgb_deep_x.npy")!r}
r = score_file(loaded, rows, kind="margin", chunk_rows=10, mesh=mesh)
rp = score_file(loaded, rows, kind="predict", chunk_rows=10, mesh=mesh)
print(json.dumps({{"margin": r.values.tolist(), "predict": rp.values.tolist(),
                  "devices": r.engine["devices"]}}))
""")
    assert res["devices"] == 8
    want = np.asarray(exp["raw_margin"], dtype=np.float32)
    np.testing.assert_allclose(np.asarray(res["margin"], np.float32), want,
                               rtol=1e-5, atol=1e-6)
    # regression fixture: predictions ARE margins (engine tolerance)
    np.testing.assert_allclose(np.asarray(res["predict"], np.float32),
                               np.asarray(exp["predict"]),
                               rtol=1e-5, atol=1e-6)


def test_score_fixture_matches_expected_record():
    """The committed .npy must stay the expected.json queries, byte for
    byte (make_fixtures.py regenerates it)."""
    exp = json.loads(
        (FIXTURES / "ingest" / "xgb_deep.expected.json").read_text()
    )
    x = np.load(FIXTURES / "score" / "xgb_deep_x.npy")
    np.testing.assert_array_equal(x, np.asarray(exp["x"], dtype=np.float64))


def test_score_cli_expected_round_trip(tmp_path):
    """The CI score-golden job's exact path: ingest CLI -> score CLI
    --expected, in a subprocess (exercises the shared _cli plumbing)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # single device is fine and faster here
    ingest = subprocess.run(
        [sys.executable, str(SCRIPTS / "ingest.py"),
         str(FIXTURES / "ingest" / "xgb_deep.json"),
         "--out", str(tmp_path / "art")],
        capture_output=True, text=True, env=env,
    )
    assert ingest.returncode == 0, ingest.stderr
    score = subprocess.run(
        [sys.executable, str(SCRIPTS / "score.py"), str(tmp_path / "art"),
         str(FIXTURES / "score" / "xgb_deep_x.npy"),
         "--expected", str(FIXTURES / "ingest" / "xgb_deep.expected.json"),
         "--chunk-rows", "10"],
        capture_output=True, text=True, env=env,
    )
    assert score.returncode == 0, score.stdout + score.stderr
    assert "[verify]  OK" in score.stdout


# -- public surface ------------------------------------------------------------


def test_repro_all_resolves():
    """Every documented name in repro.__all__ must import — the README
    module map contract."""
    for name in repro.__all__:
        assert getattr(repro, name) is not None, name
    # and the score package's own surface
    import repro.score as sc

    for name in sc.__all__:
        assert getattr(sc, name) is not None, name
    assert "score_file" in repro.__all__
    assert "CompiledModel" in repro.__all__


def test_score_result_reports_throughput(binary_cm):
    cm, q, _, _ = binary_cm
    r = score_file(cm, q, kind="predict", chunk_rows=100)
    assert isinstance(r, ScoreResult)
    assert r.n_rows == q.shape[0] and r.n_chunks == 4
    assert r.elapsed_s > 0 and r.rows_per_s > 0
    assert r.engine["kernel"].startswith("v")
    assert set(r.engine) >= {"backend", "table_dtype", "kernel",
                             "noc_config", "devices"}
