"""Kernel v3 (DESIGN.md §13): measured v1/v2 dispatch, fused epilogue,
sparsity-aware column clustering — every variant gated by the shared
differential oracle (tests/oracles.py).

The dispatch table is a PERFORMANCE artifact: whichever kernel version
the autotuner's timings pick for a bucket, the bound engine must stay
bit-equal to the v1 int32 oracle.  These tests therefore never assert on
timings (nondeterministic) — only that every reachable dispatch outcome
passes the oracle gate and that the plan round-trips byte-exactly
through the artifact sidecar.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st
from oracles import (
    assert_bit_equal_to_oracle,
    compact_problem,
    env_interpret,
    env_interpret_kernel,
    random_cam_table,
)

import jax.numpy as jnp

from repro.api import CompiledModel, build
from repro.core.compile import order_columns_by_activity
from repro.core.deploy import DeployConfig
from repro.core.engine import XTimeEngine
from repro.core.tune import TunePlan, autotune_kernel, kernel_version
from repro.kernels.cam_match import cam_match_pallas, full_tile_mask

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
FIXTURES = Path(__file__).parent / "fixtures" / "ingest"


# -- v1/v2 dispatch ------------------------------------------------------------


def test_kernel_version_axis():
    assert kernel_version("int32") == "v1"
    assert kernel_version("uint8") == "v2"
    assert kernel_version("uint16") == "v2"


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 10_000), r=st.integers(2, 6), f=st.integers(4, 24))
def test_dispatched_kernel_bit_equal_and_plan_round_trips(seed, r, f):
    """Property across the v1/v2 crossover regime: whatever kernel the
    sweep's timings pick per bucket, the bound engine passes the oracle
    gate, and the persisted plan picks the SAME kernel version after a
    to_dict/from_dict round trip."""
    rng = np.random.default_rng(seed)
    table = random_cam_table(rng, r=32 * r, f=f, n_bins=256)
    plan = autotune_kernel(
        table,
        deploy=DeployConfig(backend="pallas", interpret=env_interpret()),
        batch=32, batches=(8, 96), b_blks=(32,), r_blks=(32, 64),
        warmup=1, iters=1, seed=seed,
    )
    assert [e["batch"] for e in plan.dispatch] == [8, 32, 96]
    restored = TunePlan.from_dict(json.loads(json.dumps(plan.to_dict())))
    assert restored == plan
    q = rng.integers(0, 256, size=(48, f))
    for b in (8, 32, 96):
        e = plan.dispatch_for(b)
        assert e["kernel"] == kernel_version(e["table_dtype"])
        assert restored.dispatch_for(b)["kernel"] == e["kernel"]
        cfg = plan.apply(
            DeployConfig(backend="pallas", interpret=env_interpret()), batch=b,
        )
        assert kernel_version(cfg.table_dtype) == e["kernel"]
        assert_bit_equal_to_oracle(table, q, cfg)


def test_handcrafted_dispatch_cold_start_binds_per_bucket(tmp_path):
    """Deterministic dispatch semantics, no timing dependence: a
    hand-written dispatch table must survive save -> load and bind the
    named kernel per serving bucket, from the artifact and from the
    registry."""
    from repro.core.trees import random_deep_ensemble
    from repro.serve.registry import TableRegistry

    ens = random_deep_ensemble(n_trees=6, depth=4, n_features=10,
                               n_bins=256, seed=0)
    cm = build(ens, deploy=DeployConfig(backend="pallas",
                                        interpret=env_interpret()))
    plan = TunePlan(
        b_blk=64, r_blk=64, table_dtype="uint8", mode="direct",
        backend="pallas", us_per_call=2.0, batch=256,
        dispatch=[
            {"batch": 16, "b_blk": 32, "r_blk": 64, "table_dtype": "int32",
             "mode": "direct", "kernel": "v1", "us_per_call": 1.0},
            {"batch": 256, "b_blk": 64, "r_blk": 64, "table_dtype": "uint8",
             "mode": "direct", "kernel": "v2", "us_per_call": 2.0},
        ],
    )
    cm.with_tuning(plan).save(tmp_path / "art")
    loaded = CompiledModel.load(tmp_path / "art")
    assert loaded.tune_plan() == plan

    e_small = loaded.engine(batch_hint=8)  # -> bucket 16: v1 int32
    e_large = loaded.engine(batch_hint=200)  # -> bucket 256: v2 uint8
    e_over = loaded.engine(batch_hint=10_000)  # beyond all -> largest
    assert (e_small.b_blk, e_small.table_dtype) == (32, "int32")
    assert (e_large.b_blk, e_large.table_dtype) == (64, "uint8")
    assert e_over is e_large  # same bucket -> memoized engine
    assert loaded.engine(batch_hint=16) is e_small

    reg = TableRegistry()
    reg.register("m", loaded)
    assert reg.engine_for_batch("m", 8).table_dtype == "int32"
    assert reg.engine_for_batch("m", 200).table_dtype == "uint8"
    # untuned artifacts keep the default engine
    reg.register("plain", cm)
    assert reg.engine_for_batch("plain", 8) is reg.engine("plain")

    # both bucket winners pass the oracle gate on the same queries
    rng = np.random.default_rng(1)
    q = rng.integers(0, 256, size=(24, 10))
    for b in (8, 200):
        cfg = plan.apply(loaded.deploy, batch=b)
        assert_bit_equal_to_oracle(loaded.table, q, cfg)


def test_schema_v1_plan_loads_with_dispatch_fallback():
    """Plans persisted before the dispatch table (schema v1) must load
    and resolve every batch to the synthesized top-level winner."""
    v1_dict = {
        "b_blk": 128, "r_blk": 256, "table_dtype": "uint8",
        "mode": "direct", "backend": "pallas", "us_per_call": 3.5,
        "batch": 256, "trials": [], "env": {}, "schema_version": 1,
    }
    plan = TunePlan.from_dict(v1_dict)
    assert plan.dispatch == []
    for b in (1, 256, 99_999):
        e = plan.dispatch_for(b)
        assert (e["b_blk"], e["table_dtype"], e["kernel"]) == (128, "uint8", "v2")
    cfg = plan.apply(DeployConfig(), batch=64)
    assert (cfg.b_blk, cfg.table_dtype) == (128, "uint8")


# -- tile-mask fallback (the silent-fallback fix) ------------------------------


def _mask_problem():
    rng = np.random.default_rng(21)
    return compact_problem(rng, 32, 64, 256, 4)


def test_none_mask_is_exactly_full_tile_mask():
    """tile_mask=None must be the EXPLICIT every-tile-active fallback:
    bit-identical output to passing full_tile_mask, never a silent skip."""
    q, low, high, leaf = _mask_problem()
    kw = dict(b_blk=32, r_blk=32, f_blk=128, mode="inclusive",
              interpret=env_interpret_kernel())
    out_none = cam_match_pallas(
        jnp.asarray(q), jnp.asarray(low.T), jnp.asarray(high.T),
        jnp.asarray(leaf), None, **kw,
    )
    out_full = cam_match_pallas(
        jnp.asarray(q), jnp.asarray(low.T), jnp.asarray(high.T),
        jnp.asarray(leaf), full_tile_mask(2, 2), **kw,
    )
    np.testing.assert_array_equal(np.asarray(out_none), np.asarray(out_full))
    # the helper itself: all-ones int32 of the grid shape
    m = np.asarray(full_tile_mask(3, 5))
    assert m.shape == (3, 5) and m.dtype == np.int32 and (m == 1).all()


@pytest.mark.parametrize("bad_shape", [(1, 2), (2, 1), (4, 4), (2, 2, 1)])
def test_misshapen_tile_mask_rejected(bad_shape):
    """A wrong-shape mask used to slip through under interpret mode and
    silently skip live tiles; it must be rejected eagerly, naming the
    expected grid shape."""
    q, low, high, leaf = _mask_problem()
    with pytest.raises(ValueError, match=r"\(2, 2\)"):
        cam_match_pallas(
            jnp.asarray(q), jnp.asarray(low.T), jnp.asarray(high.T),
            jnp.asarray(leaf), jnp.ones(bad_shape, jnp.int32),
            b_blk=32, r_blk=32, f_blk=128, mode="inclusive",
            interpret=env_interpret_kernel(),
        )


# -- fused epilogue ------------------------------------------------------------


def test_fused_epilogue_resolution_and_bit_equality():
    """'auto' fuses exactly on eligible engines (pallas, no mesh); fused
    margins are bit-equal to the unfused v1 oracle (same float order)."""
    rng = np.random.default_rng(31)
    table = random_cam_table(rng, r=64, f=12, n_bins=256)
    assert table.base_score != 0.0  # the fusion must actually add something
    q = rng.integers(0, 256, size=(40, 12))

    auto = XTimeEngine.from_config(
        table, DeployConfig(backend="pallas", b_blk=32, r_blk=32,
                            interpret=env_interpret()),
    )
    assert auto.fuse_epilogue is True
    jnp_eng = XTimeEngine.from_config(table, DeployConfig(backend="jnp"))
    assert jnp_eng.fuse_epilogue is False

    for fuse in (True, False, "auto"):
        cfg = DeployConfig(backend="pallas", b_blk=32, r_blk=32,
                           fuse_epilogue=fuse, interpret=env_interpret())
        assert_bit_equal_to_oracle(table, q, cfg)


def test_fuse_forced_on_ineligible_engine_raises():
    rng = np.random.default_rng(32)
    table = random_cam_table(rng, r=32, f=8)
    with pytest.raises(ValueError, match="fuse_epilogue"):
        XTimeEngine.from_config(
            table, DeployConfig(backend="jnp", fuse_epilogue=True),
        )
    with pytest.raises(ValueError):
        DeployConfig(fuse_epilogue="yes")


# -- column clustering ---------------------------------------------------------


def test_column_clustering_zero_cost_wildcard_features():
    """All-wildcard FEATURE columns must become skippable tiles after
    clustering — with margins bit-equal to the unclustered table (the
    match line is a boolean AND: column order cannot change any bit)."""
    rng = np.random.default_rng(41)
    table = random_cam_table(rng, r=64, f=32, n_bins=256, n_outputs=2)
    # constrain only 6 interleaved features; the rest are pure wildcards
    low, high = table.low.copy(), table.high.copy()
    low[:, :], high[:, :] = 0, 256
    keep = np.arange(0, 32, 5)
    low[:, keep], high[:, keep] = table.low[:, keep], table.high[:, keep]
    import dataclasses
    table = dataclasses.replace(table, low=low, high=high)

    clustered = order_columns_by_activity(table, f_blk=8)
    assert clustered.col_perm is not None
    assert clustered.tile_skip_fraction(32, 8) > table.tile_skip_fraction(32, 8)
    # active features all precede inactive ones in the permuted layout
    occ = clustered.feature_occupancy()
    n_active = int((table.feature_occupancy() > 0).sum())
    assert (occ[:n_active] > 0).all() and (occ[n_active:] == 0).all()

    q = rng.integers(0, 256, size=(24, 32))
    cfg = DeployConfig(backend="pallas", b_blk=8, r_blk=32, f_blk=8,
                       interpret=env_interpret())
    m_clustered = assert_bit_equal_to_oracle(clustered, q, cfg)
    m_plain = np.asarray(
        XTimeEngine.from_config(table, cfg).raw_margin(q)
    )
    np.testing.assert_array_equal(m_clustered, m_plain)


def test_xgb_deep_clustering_golden_save_load_bind(tmp_path):
    """The golden xgb_deep fixture (only 2 of 5 features ever split):
    cluster_columns build must move the 3 wildcard columns to trailing
    tiles, survive save -> load -> engine bind, and reproduce the frozen
    float record BIT-exactly (k/16 leaves: any order is exact)."""
    dump = FIXTURES / "xgb_deep.json"
    exp = json.loads(
        (FIXTURES / "xgb_deep.expected.json").read_text()
    )
    x = np.asarray(exp["x"], dtype=np.float64)
    record = np.asarray(exp["raw_margin"], dtype=np.float32)

    cfg = DeployConfig(backend="pallas", b_blk=8, r_blk=32, f_blk=2,
                       interpret=env_interpret())
    cm = build(str(dump), deploy=cfg, cluster_columns=True)
    perm = cm.table.col_perm
    assert perm is not None
    assert not np.array_equal(perm, np.arange(cm.table.n_cols))
    # the permuted layout packs both live features into the first tile
    assert (cm.table.feature_occupancy()[2:] == 0).all()

    xb = cm.quantizer.transform(x)
    np.testing.assert_array_equal(np.asarray(cm.engine().raw_margin(xb)), record)

    cm.save(tmp_path / "art")
    loaded = CompiledModel.load(tmp_path / "art")
    np.testing.assert_array_equal(loaded.table.col_perm, perm)
    np.testing.assert_array_equal(
        np.asarray(loaded.engine().raw_margin(loaded.quantizer.transform(x))), record,
    )
    assert_bit_equal_to_oracle(loaded.table, loaded.quantizer.transform(x), cfg)


_SHARD_CODE = """
import json
import numpy as np
from pathlib import Path
from repro.api import build
from repro.core.deploy import DeployConfig
from repro.launch.mesh import make_host_mesh

dump = Path({dump!r})
exp = json.loads(dump.with_name("xgb_deep.expected.json").read_text())
x = np.asarray(exp["x"], dtype=np.float64)
record = np.asarray(exp["raw_margin"], dtype=np.float32)

cm = build(str(dump), cluster_columns=True)
assert cm.table.col_perm is not None
xb = cm.quantizer.transform(x)
mesh = make_host_mesh()
out = {{}}
for spmd in ("shard_map", "gspmd"):
    eng = cm.engine(mesh=mesh, spmd=spmd)
    m = np.asarray(eng.raw_margin(xb))
    out[spmd] = {{
        "bit_equal": bool(np.array_equal(m, record)),
        "max_err": float(np.abs(m - record).max()),
    }}
print(json.dumps(out))
"""


def test_clustered_artifact_bit_equal_under_shard_map():
    """Column clustering is a query-side permutation — it must commute
    with BOTH spmd paths on 8 fake devices, reproducing the golden
    record (k/16 leaves make even psum reordering exact)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    code = _SHARD_CODE.format(dump=str(FIXTURES / "xgb_deep.json"))
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    results = json.loads(out.stdout.strip().splitlines()[-1])
    for spmd, res in results.items():
        assert res["bit_equal"], (spmd, res)
