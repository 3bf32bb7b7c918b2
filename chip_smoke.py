"""Bring-up smoke: the X-TIME scoring and serving path on a TPU chip.

    python chip_smoke.py                # one chip: phases a, b, c
    python chip_smoke.py --four-chips   # four chips: phase a's table, sharded
    python chip_smoke.py --rehearse     # CPU dry run at reduced sizes

Phases, each checked against the repo's plain reference:

  a  offline, paper scale (configs/xtime_tabular.py): a 4096-tree depth-8
     ensemble over 130 features and 8 classes (1,048,576 CAM rows) goes
     build(backend='pallas') -> save -> CompiledModel.load -> score_file
     over a seeded 65,536-row .npy.  Leaves are k/16, so float32 sums are
     exact and the margins must be BIT-EQUAL to Ensemble.raw_margin.
  b  online: a GBDT trained on the gas Table-II analog (129 features, 6
     classes) is served from a two-replica ClusterServer under a seeded
     heavy-tailed trace.  Every prediction must equal Ensemble.predict,
     margins must sit within the engine tolerance of Ensemble.raw_margin,
     and the run must show no failover, failed or shed request and no
     replica error.
  c  soft cell mode on b's model: at tau=0 predictions equal the direct
     engine's, and predict_proba rows sum to 1.

--four-chips runs only phase a's table: rows sharded over a (1, 4) mesh
('accumulate', a psum over shard_map) and score_file under the 'batch'
program (table replicated, queries split); both bit-equal to the
reference, and each chip must hold a quarter of the rows when sharded.

Every phase asserts that its bound engine runs the compiled Pallas kernel
(interpret off, ``tpu_custom_call`` in the compiled HLO).  A platform
other than TPU is an error unless --rehearse is given.  Progress goes to
stdout; the last line is the JSON result, printed only when every phase
passed.  Any failure exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import logging
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
WORK = ROOT / ".chip_smoke"  # git-ignored scratch for artifacts and rows
SEED = 20261016

# full size: phase a is the paper's maximum ensemble; --rehearse shrinks
# depth and counts but keeps every width
FULL = {"trees": 4096, "depth": 8, "rows": 65_536, "chunk": 8192,
        "rounds": 25, "requests": 3000}
REHEARSE = {"trees": 32, "depth": 6, "rows": 512, "chunk": 256,
            "rounds": 4, "requests": 300}
N_FEATURES, N_BINS, N_CLASSES = 130, 256, 8  # configs/xtime_tabular.py


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _check_engine(phase: str, eng, on_tpu: bool, *, collective: str = "") -> None:
    """The bound engine runs the compiled Pallas kernel (and, on a mesh,
    the named collective)."""
    _check(eng.backend == "pallas", f"{phase}: backend {eng.backend!r}")
    _check(eng.interpret is (not on_tpu),
           f"{phase}: interpret={eng.interpret} on this platform")
    if on_tpu:
        hlo = eng.compiled_text("margin")
        _check("tpu_custom_call" in hlo, f"{phase}: no tpu_custom_call in HLO")
        if collective:
            _check(collective in hlo, f"{phase}: no {collective} in HLO")
    _log(phase, f"engine: pallas/{eng.table_dtype} mode {eng.kernel_mode}, "
                f"interpret={eng.interpret}, spmd {eng.spmd}, "
                f"noc {eng.noc_config}")


def _paper_model(size: dict):
    """Phase a's ensemble, built for the Pallas backend."""
    from repro.api import build
    from repro.core.deploy import DeployConfig
    from repro.core.trees import random_deep_ensemble

    t0 = time.perf_counter()
    ens = random_deep_ensemble(
        n_trees=size["trees"], depth=size["depth"], n_features=N_FEATURES,
        n_bins=N_BINS, task="multiclass", n_classes=N_CLASSES, seed=SEED,
    )
    cm = build(ens, deploy=DeployConfig(backend="pallas"))
    _log("a", f"set-up: {ens.n_trees} trees depth {size['depth']} -> "
              f"{cm.table.n_rows} CAM rows x {cm.table.n_cols} features, "
              f"built in {time.perf_counter() - t0:.1f} s")
    rows = np.random.default_rng(SEED).integers(
        0, N_BINS, size=(size["rows"], N_FEATURES)
    ).astype(np.uint8)
    path = WORK / "rows.npy"
    np.save(path, rows)
    t0 = time.perf_counter()
    ref = ens.raw_margin(rows)
    _log("a", f"reference: {rows.shape[0]} rows in "
              f"{time.perf_counter() - t0:.1f} s")
    return ens, cm, path, ref


def _compare_margins(phase: str, got: np.ndarray, ref: np.ndarray) -> None:
    err = float(np.abs(got.astype(np.float64) - ref).max())
    _log(phase, f"max margin error vs Ensemble.raw_margin: {err!r}")
    _check(got.shape == ref.shape, f"{phase}: shape {got.shape} != {ref.shape}")
    _check(np.array_equal(got, ref), f"{phase}: margins not bit-equal")


def phase_offline(size: dict, on_tpu: bool) -> None:
    """a: paper-scale build -> save -> load -> score_file, bit-equal."""
    from repro.api import CompiledModel
    from repro.score import score_file

    _, cm, path, ref = _paper_model(size)
    t0 = time.perf_counter()
    cm.save(WORK / "paper")
    del cm
    loaded = CompiledModel.load(WORK / "paper")
    _check(loaded.deploy.backend == "pallas", "a: backend lost in save/load")
    _log("a", f"save + load: {time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    res = score_file(loaded, path, kind="margin", chunk_rows=size["chunk"])
    _log("a", f"score_file: {res.n_rows} rows in {res.n_chunks} chunks "
              f"(bucket {res.bucket}), {time.perf_counter() - t0:.1f} s "
              "including bind and compile")
    _compare_margins("a", np.asarray(res.values), ref)
    _check_engine("a", loaded.engine(batch_hint=size["chunk"]), on_tpu)


class _ErrorLog(logging.Handler):
    """Collects WARNING+ records of the serving tier (replica failures,
    dispatcher errors): any record fails phase b."""

    def __init__(self) -> None:
        super().__init__(logging.WARNING)
        self.records: list[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.records.append(self.format(record))


def _gas_model(size: dict):
    from repro.core.quantize import FeatureQuantizer
    from repro.core.trees import GBDTParams, train_gbdt
    from repro.data.tabular import make_dataset

    t0 = time.perf_counter()
    ds = make_dataset("gas")
    quant = FeatureQuantizer.fit(ds.x_train, N_BINS)
    ens = train_gbdt(
        quant.transform(ds.x_train), ds.y_train, task=ds.task, n_bins=N_BINS,
        n_classes=ds.n_classes,
        params=GBDTParams(n_rounds=size["rounds"], max_leaves=64,
                          learning_rate=0.15),
    )
    xb = quant.transform(ds.x_test)
    _log("b", f"set-up: gas GBDT {ens.n_trees} trees, {ds.n_features} "
              f"features, {ds.n_classes} classes, trained in "
              f"{time.perf_counter() - t0:.1f} s")
    return ens, xb


def phase_online(size: dict, on_tpu: bool, ens, xb: np.ndarray) -> None:
    """b: ClusterServer(2 replicas, pallas) under a seeded trace."""
    from repro.core.deploy import DeployConfig
    from repro.serve.cluster import ClusterServer, ShedError
    from repro.serve.traffic import make_trace, replay_trace

    errors = _ErrorLog()
    serve_log = logging.getLogger("repro.serve")
    serve_log.addHandler(errors)
    server = ClusterServer(
        n_replicas=2, deploy=DeployConfig(backend="pallas"),
        run_dir=str(WORK / "cluster"),
    )
    try:
        t0 = time.perf_counter()
        entry = server.register("gas", ens)  # compiles every serving bucket
        _log("b", f"register: {time.perf_counter() - t0:.1f} s")
        eng = entry.engine
        _check_engine("b", eng, on_tpu)
        m = np.asarray(eng.raw_margin(xb))
        ref = ens.raw_margin(xb)
        err = float(np.abs(m.astype(np.float64) - ref).max())
        _log("b", f"max margin error vs Ensemble.raw_margin: {err!r} "
                  f"(bit-equal: {bool(np.array_equal(m, ref))})")
        _check(np.allclose(m, ref, rtol=1e-5, atol=1e-6),
               "b: margins outside engine tolerance")

        trace = make_trace(["gas"], size["requests"], seed=SEED)
        t0 = time.perf_counter()
        rep = replay_trace(server.submit, trace, {"gas": xb},
                           shed_exceptions=(ShedError,))
        want = ens.predict(xb)
        n = xb.shape[0]
        for req, h in zip(trace.requests, rep.handles):
            _check(h is not None, f"b: request at {req.t:.4f}s shed")
            got = h.result(timeout=120.0)
            idx = np.arange(req.row_start, req.row_start + req.n_rows) % n
            _check(np.array_equal(got, want[idx]),
                   f"b: request {h.request_id} predictions differ")
        server.drain(timeout=60.0)
        report = server.report("gas")
        _log("b", f"{len(trace.requests)} requests / {trace.n_rows} rows "
                  f"served in {time.perf_counter() - t0:.1f} s, "
                  f"{report['measured']['flushes']} flushes, failovers "
                  f"{report['failovers']}, shed {report['shed']}")
        _check(rep.shed == 0 and not report["shed"], "b: requests shed")
        _check(report["failovers"] == 0, "b: replica failover")
        _check(all(r["state"] == "alive" for r in report["replicas"].values()),
               f"b: replica states {report['replicas']}")
        _check(not errors.records, f"b: serving errors {errors.records}")
    finally:
        server.close()
        serve_log.removeHandler(errors)


def phase_soft(on_tpu: bool, ens, xb: np.ndarray) -> None:
    """c: soft mode on b's model — tau=0 == direct, probabilities sum to 1."""
    from repro.api import build
    from repro.core.deploy import DeployConfig

    cm = build(ens, deploy=DeployConfig(backend="pallas"))
    direct = cm.engine()
    hard = cm.engine(mode="soft", tau=0.0)
    _check_engine("c", hard, on_tpu)
    _check(np.array_equal(np.asarray(hard.predict(xb)),
                          np.asarray(direct.predict(xb))),
           "c: tau=0 predictions differ from direct")
    diff = float(np.abs(np.asarray(hard.raw_margin(xb))
                        - np.asarray(direct.raw_margin(xb))).max())
    _log("c", f"tau=0 vs direct: predictions equal, max margin diff {diff!r}")
    soft = cm.with_deploy(cm.deploy.replace(mode="soft"))
    _check_engine("c", soft.engine(), on_tpu)
    p = soft.predict_proba(xb)
    row_err = float(np.abs(p.astype(np.float64).sum(axis=1) - 1.0).max())
    _log("c", f"predict_proba tau={soft.deploy.tau}: {p.shape}, max "
              f"|row sum - 1| {row_err!r}")
    _check(bool(np.isfinite(p).all()), "c: non-finite probabilities")
    _check(row_err <= 1e-5, "c: predict_proba rows do not sum to 1")


def phase_four_chips(size: dict, on_tpu: bool) -> None:
    """Phase a's table on four chips: 'accumulate' and 'batch'."""
    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.score import score_file

    _check(len(jax.devices()) == 4, f"need 4 devices, have {jax.devices()}")
    _, cm, path, ref = _paper_model(size)
    mesh = make_host_mesh(1, 4)
    for noc in ("accumulate", "batch"):
        t0 = time.perf_counter()
        res = score_file(cm, path, kind="margin", chunk_rows=size["chunk"],
                         mesh=mesh, noc_config=noc)
        _log("4", f"{noc}: {res.n_rows} rows in {res.n_chunks} chunks "
                  f"(bucket {res.bucket}) over {res.engine['devices']} "
                  f"devices, {time.perf_counter() - t0:.1f} s including "
                  "bind and compile")
        _compare_margins(f"4/{noc}", np.asarray(res.values), ref)
        eng = cm.engine(mesh=mesh, batch_hint=size["chunk"], noc_config=noc)
        _check(eng.spmd == "shard_map", f"4/{noc}: spmd {eng.spmd}")
        shards = eng.arrays.low.addressable_shards  # (F_pad, rows) each
        rows = sorted({s.data.shape[1] for s in shards})
        _check(len({s.device for s in shards}) == 4,
               f"4/{noc}: table on {len(shards)} shards")
        want = eng.arrays.r_pad // 4 if noc == "accumulate" else eng.arrays.r_pad
        _check(rows == [want], f"4/{noc}: rows per chip {rows}, want {want}")
        _log("4", f"{noc}: {rows[0]} of {eng.arrays.r_pad} table rows on "
                  "each chip")
        _check_engine(f"4/{noc}", eng, on_tpu,
                      collective="all-reduce" if noc == "accumulate" else "")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run phase a's table sharded over four chips only")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU dry run at reduced sizes (not a chip result)")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r}); "
              "--rehearse runs a CPU dry run", file=sys.stderr)
        return 1
    size = REHEARSE if args.rehearse else FULL
    print(f"[env] {len(devices)} x {dev.device_kind} ({dev.platform}), "
          f"jax {jax.__version__}, compile cache {cache}", flush=True)

    WORK.mkdir(exist_ok=True)
    t_all = time.perf_counter()
    try:
        if args.four_chips:
            phase_four_chips(size, on_tpu)
        else:
            phase_offline(size, on_tpu)
            ens, xb = _gas_model(size)
            phase_online(size, on_tpu, ens, xb)
            phase_soft(on_tpu, ens, xb)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    print(f"[env] all phases passed in {time.perf_counter() - t_all:.1f} s",
          flush=True)
    result = {"ok": True, "device": {"platform": dev.platform,
                                     "kind": dev.device_kind,
                                     "count": len(devices)}}
    if args.rehearse:
        result["rehearse"] = True
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
