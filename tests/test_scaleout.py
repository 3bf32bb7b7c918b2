"""Scale-out equivalence: the shard_map and GSPMD engine paths against the
single-device engine, exhaustively over (spmd × noc_config × cell mode)
— the DESIGN.md §8 bit-equivalence guarantee.

The multi-device sweep runs in the shared 8-fake-host-device subprocess
harness (``oracles.run_on_8_devices``): ONE subprocess builds the model
and loops the whole configuration grid (amortizing training/compile),
printing per-config max errors as JSON.  The guarantee it asserts:

  * shard_map and GSPMD produce BIT-IDENTICAL margins to each other
    (same per-shard partial sums, same reduction tree), and
  * both match the single-device engine within one float32 ULP of
    reduction reordering, with predictions exactly equal.
"""

import numpy as np
import pytest

from oracles import run_on_8_devices
from repro.core.deploy import DeployConfig

# -- config-level behaviour (no mesh needed) -----------------------------------


def test_deploy_config_spmd_validation():
    assert DeployConfig().spmd == "auto"
    DeployConfig(spmd="gspmd")
    DeployConfig(spmd="shard_map")
    with pytest.raises(ValueError):
        DeployConfig(spmd="magic")
    with pytest.raises(ValueError):
        DeployConfig(noc_config="sideways")


def test_deploy_config_hybrid_and_spmd_round_trip():
    cfg = DeployConfig(noc_config="hybrid", spmd="shard_map")
    assert DeployConfig.from_dict(cfg.to_dict()) == cfg
    # pre-spmd sidecars (schema v1 artifacts saved before the field
    # existed) must still load, defaulting to 'auto'
    legacy = {k: v for k, v in cfg.to_dict().items() if k != "spmd"}
    assert DeployConfig.from_dict(legacy).spmd == "auto"


def test_engine_resolves_spmd_without_mesh():
    from repro.core.compile import compile_ensemble
    from repro.core.engine import XTimeEngine
    from repro.core.trees import GBDTParams, train_gbdt

    rng = np.random.default_rng(0)
    xb = rng.integers(0, 16, size=(64, 4))
    y = (xb.sum(1) > 30).astype(np.int64)
    ens = train_gbdt(xb, y, task="binary", n_bins=16,
                     params=GBDTParams(n_rounds=2, max_leaves=4))
    table = compile_ensemble(ens)
    # no mesh: both 'auto' and an explicit 'shard_map' degrade to plain jit
    assert XTimeEngine(table, config=DeployConfig()).spmd == "gspmd"
    eng = XTimeEngine(table, config=DeployConfig(spmd="shard_map"))
    assert eng.spmd == "gspmd"
    np.testing.assert_allclose(
        np.asarray(eng.raw_margin(xb)), ens.raw_margin(xb),
        rtol=1e-4, atol=1e-5,
    )


def test_resolved_deploy_spmd_from_mesh():
    from repro.api import build
    from repro.core.trees import GBDTParams, train_gbdt
    from repro.launch.mesh import make_host_mesh

    rng = np.random.default_rng(0)
    xb = rng.integers(0, 16, size=(64, 4))
    y = (xb.sum(1) > 30).astype(np.int64)
    cm = build(train_gbdt(xb, y, task="binary", n_bins=16,
                          params=GBDTParams(n_rounds=2, max_leaves=4)))
    assert cm.resolved_deploy(mesh=None).spmd == "gspmd"
    mesh = make_host_mesh()
    assert cm.resolved_deploy(mesh=mesh).spmd == "shard_map"
    assert cm.resolved_deploy(mesh=mesh, spmd="gspmd").spmd == "gspmd"
    # the resolved engine actually binds in the resolved mode
    assert cm.engine(mesh=mesh).spmd == "shard_map"


# -- the 8-device property sweep -----------------------------------------------

_SWEEP = r"""
import json, numpy as np
import jax
from repro.core.compile import compile_ensemble
from repro.core.deploy import DeployConfig
from repro.core.engine import XTimeEngine
from repro.core.quantize import FeatureQuantizer
from repro.core.trees import train_gbdt, GBDTParams
from repro.data.tabular import make_dataset
from repro.launch.mesh import make_host_mesh

ds = make_dataset("eye")
q = FeatureQuantizer.fit(ds.x_train, 256)
xb = q.transform(ds.x_train)[:64]
ens = train_gbdt(q.transform(ds.x_train), ds.y_train, task="multiclass",
                 n_bins=256, n_classes=ds.n_classes,
                 params=GBDTParams(n_rounds=3, max_leaves=16))
table = compile_ensemble(ens)
mesh = make_host_mesh(2, 4)

results = {"n_dev": len(jax.devices()), "cases": []}
MODES = ("direct", "inclusive", "msb_lsb", "two_cycle")
NOCS = ("accumulate", "batch", "hybrid")

for mode in MODES:
    # single-device reference engine for this cell mode
    e0 = XTimeEngine(table, config=DeployConfig(mode=mode))
    m0 = np.asarray(e0.raw_margin(xb))
    p0 = np.asarray(e0.predict(xb))
    for noc in NOCS:
        margins = {}
        for spmd in ("gspmd", "shard_map"):
            if noc == "hybrid" and spmd == "gspmd":
                continue  # hybrid is shard_map-only by construction
            cfg = DeployConfig(mode=mode, noc_config=noc, spmd=spmd)
            e = XTimeEngine(table, config=cfg, mesh=mesh)
            m = np.asarray(e.raw_margin(xb))
            p = np.asarray(e.predict(xb))
            margins[spmd] = m
            results["cases"].append({
                "mode": mode, "noc": noc, "spmd": spmd,
                "maxerr_vs_single": float(np.abs(m - m0).max()),
                "pred_equal": bool((p == p0).all()),
            })
        if len(margins) == 2:
            results["cases"][-1]["bitwise_vs_gspmd"] = bool(
                (margins["gspmd"] == margins["shard_map"]).all()
            )

# pallas backend spot-check under shard_map (interpret mode; small tiles)
for noc in NOCS:
    cfg = DeployConfig(backend="pallas", b_blk=8, r_blk=64,
                       noc_config=noc, spmd="shard_map")
    e = XTimeEngine(table, config=cfg, mesh=mesh)
    m = np.asarray(e.raw_margin(xb))
    e0 = XTimeEngine(table, config=DeployConfig())
    results["cases"].append({
        "mode": "direct", "noc": noc, "spmd": "shard_map", "backend": "pallas",
        "maxerr_vs_single": float(np.abs(m - np.asarray(e0.raw_margin(xb))).max()),
        "pred_equal": bool(
            (np.asarray(e.predict(xb)) == np.asarray(e0.predict(xb))).all()
        ),
    })
print(json.dumps(results))
"""


def test_spmd_paths_match_single_device_all_modes():
    res = run_on_8_devices(_SWEEP)
    assert res["n_dev"] == 8
    # jnp grid: 4 modes x (accumulate, batch: 2 spmds; hybrid: 1) = 20,
    # plus 3 pallas spot-checks
    assert len(res["cases"]) == 23
    for case in res["cases"]:
        # <= 1 float32 ULP of reduction reordering at these magnitudes
        assert case["maxerr_vs_single"] < 1e-5, case
        assert case["pred_equal"], case
        if "bitwise_vs_gspmd" in case:
            assert case["bitwise_vs_gspmd"], case


_SERVE_SWEEP = r"""
import json, numpy as np
import jax
from repro.api import build
from repro.core.deploy import DeployConfig
from repro.core.quantize import FeatureQuantizer
from repro.core.trees import train_gbdt, GBDTParams
from repro.data.tabular import make_dataset
from repro.launch.mesh import make_host_mesh
from repro.serve import ServeLoop, TableRegistry

ds = make_dataset("churn")
q = FeatureQuantizer.fit(ds.x_train, 256)
xb = q.transform(ds.x_train)[:32].astype(np.int32)
ens = train_gbdt(q.transform(ds.x_train), ds.y_train, task="binary",
                 n_bins=256, params=GBDTParams(n_rounds=3, max_leaves=16))
cm = build(ens)
mesh = make_host_mesh(2, 4)
reg = TableRegistry(mesh=mesh)
entry = reg.register("m", cm)
loop = ServeLoop(reg, window_s=10.0, flush_rows=64)
handles = [loop.submit("m", row) for row in xb]
loop.drain()
served = np.concatenate([loop.result(h) for h in handles])
expected = np.asarray(cm.engine().predict(xb))
print(json.dumps({
    "spmd": entry.engine.spmd,
    "n_dev": len(jax.devices()),
    "serve_equal": bool((served == expected).all()),
    "batch_multiple": entry.engine.batch_multiple,
}))
"""


def test_registry_serves_shard_map_for_free():
    """A mesh registry binds the shard_map path with no caller changes,
    and the micro-batched serving outputs still match single-device."""
    res = run_on_8_devices(_SERVE_SWEEP)
    assert res["n_dev"] == 8
    assert res["spmd"] == "shard_map"
    assert res["serve_equal"]
    # jnp backend on a (2, 4) mesh: buckets must split across 2 data shards
    assert res["batch_multiple"] == 2
