"""A whole run on the CPU at tiny sizes: the chip check, the comparison
that decides ``correct``, and that a planted fault turns it false."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from chipbench import artifact, harness, reference, spec

ROOT = spec.ROOT
PAPER = {"name": "tiny-paper", "maker": "random_trees", "seed": 1, "n_trees": 24,
         "depth": 4, "n_features": 20, "n_bins": 256, "n_classes": 3,
         "task": "multiclass", "p_dup": 0.0, "leaf_std": 0.1, "base_score": 0.5,
         "limits": {"margin_err": 1e-5}}
GAS = {"name": "tiny-gas", "maker": "gbdt_tabular", "seed": 0,
       "model_file": "chipbench/models/gas-gbdt.npz", "n_rounds": 2,
       "limits": {"margin_err": 1e-5}}
OFFLINE = {"driver": "closed_file", "metric": "offline_float_rows_per_s", "rows": "floats",
           "file_rows": 300, "chunk_rows": 128, "compare_rows": 200}
ONLINE = {"driver": "open_loop", "rows": "bins", "rate_per_s": 200, "base_seed": 3,
          "tail_alpha": 1.8, "mean_rows": 1.3, "max_rows": 8, "replicas": 2,
          "flush_rows": 64, "max_batch": 256, "max_queue_rows": 8192,
          "heartbeat_timeout_s": 10.0, "warm_s": 0.2, "compare_rows": 100}
CELLS = {"offline": (GAS, OFFLINE), "online": (PAPER, ONLINE)}


def _cell(tmp_path, kind, cfg=None, traffic=None):
    cfg = cfg or CELLS[kind][0]
    traffic = traffic or CELLS[kind][1]
    tmp_path.mkdir(parents=True, exist_ok=True)
    path = tmp_path / f"{cfg['name']}.json"
    path.write_text(json.dumps(cfg))
    e2e = ([traffic["metric"]] if kind == "offline"
           else ["latency_p99_ms", "latency_p50_ms"])
    return spec.Cell(name=kind, chips=1, config=cfg, config_path=path,
                     traffic=traffic, traffic_path=path,
                     end_to_end=[{"name": n, "unit": "x"} for n in ["setup_s", *e2e]])


def _run(tmp_path, kind, cfg=None, traffic=None):
    return harness.run_cell(_cell(tmp_path, kind, cfg, traffic), seed=2**32 + 9, seconds=0.5,
                            trace=False, t_start=time.time(),
                            cache=tmp_path / "cache", compile_cache=False)


FAULTS = {
    # one margin of every call altered where the engine produces it
    "altered_answer": lambda out: out.at[0, 0].add(1e-3),
    # half of every batch left out: every other row's margins never computed
    "half_rows_left_out": lambda out: out.at[1::2].set(0.0),
}


def _plant(monkeypatch, fault):
    """Break the timed path underneath: the engine's entry, which both
    score_file and the serving tier call, returns ``fault(margins)``."""
    from repro.core.engine import XTimeEngine

    padded_fn = XTimeEngine.padded_fn

    def broken(self, kind="predict"):
        run = padded_fn(self, kind)
        return lambda q: FAULTS[fault](run(q))

    monkeypatch.setattr(XTimeEngine, "padded_fn", broken)


@pytest.mark.parametrize("kind", ["offline", "online"])
def test_sound_run_is_correct(tmp_path, kind):
    result = _run(tmp_path, kind)
    assert result["correct"], result
    assert list(result)[-1] == "checks"
    assert result["checks"]["margin_err"]["value"] < 1e-5
    assert result["failed"] == 0 and result["attempted"] > 0
    names = set(result["metrics"])
    assert "setup_s" in names and len(names) >= 2
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("kind", ["offline", "online"])
def test_broken_timed_path_is_not_correct(tmp_path, kind, fault, monkeypatch):
    _plant(monkeypatch, fault)
    result = _run(tmp_path, kind)
    assert not result["correct"]
    assert result["checks"]["margin_err"]["value"] > result["checks"]["margin_err"]["limit"]


def test_shed_requests_are_not_correct(tmp_path):
    """A server that refuses requests fails the check, however fast the
    requests it did answer came back: a queue of one row sheds every
    request of two rows or more."""
    result = _run(tmp_path, "online", traffic={**ONLINE, "max_queue_rows": 1})
    assert not result["correct"]
    assert result["checks"]["missing"]["value"] > 0
    assert result["checks"]["margin_err"]["value"] < 1e-5
    assert result["failed"] >= result["checks"]["missing"]["value"]


def _plant_control(monkeypatch):
    """Put the control in the program's place under the timed path: every
    leaf of the table that the engine binds held to what a three-pass
    bfloat16 product keeps (``Precision.HIGH``, the precision below the
    configurations' float32), then summed by the program as before."""
    load = artifact.load_or_build

    def lowered(*args, **kwargs):
        model, built = load(*args, **kwargs)
        cm = model.compiled
        table = dataclasses.replace(cm.table, leaf=reference.round_high(cm.table.leaf))
        model.compiled = dataclasses.replace(cm, table=table)
        return model, built

    monkeypatch.setattr(artifact, "load_or_build", lowered)


# each configuration's own limit and maker, at a test size: the paper's
# random trees cut to 512 depth-6 trees of 32 features; the committed gas
# model whole, over its float rows
CONTROL = {
    "xtime-paper-max": ({"n_trees": 512, "depth": 6, "n_features": 32},
                        {**OFFLINE, "metric": "offline_rows_per_s", "rows": "bins"}),
    "gas-gbdt": ({}, OFFLINE),
}


@pytest.mark.parametrize("config", sorted(CONTROL))
def test_control_fails_the_configured_limit(tmp_path, config, monkeypatch):
    """The lower-precision control, run through the harness's own run in
    the program's place, reads ``correct: false`` against the
    configuration's limit."""
    cut, traffic = CONTROL[config]
    cfg = {**json.loads((ROOT / "chipbench" / "configs" / f"{config}.json").read_text()),
           **cut}
    traffic = {**traffic, "file_rows": 512, "chunk_rows": 512, "compare_rows": 512}
    sound = _run(tmp_path / "sound", "offline", cfg, traffic)
    assert sound["correct"], sound["checks"]
    _plant_control(monkeypatch)
    control = _run(tmp_path / "control", "offline", cfg, traffic)
    assert not control["correct"]
    err = control["checks"]["margin_err"]
    assert err["value"] > err["limit"] == cfg["limits"]["margin_err"]


def _command(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "paper-offline",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_command_fails_without_a_tpu():
    proc = _command(ROOT)
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns(".cache"))
    proc = _command(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
