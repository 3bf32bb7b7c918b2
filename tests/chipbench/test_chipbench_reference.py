"""The benchmark's plain reference against the program's own traversal."""

import json

import numpy as np
import pytest

from chipbench import artifact, reference
from chipbench.makers import gbdt_tabular, random_trees

SMALL = {"n_trees": 12, "depth": 5, "n_features": 9, "n_bins": 256,
         "n_classes": 3, "task": "multiclass", "p_dup": 0.0,
         "leaf_std": 0.1, "base_score": 0.5}


@pytest.mark.parametrize("seed", [0, 1, 2**33 + 1])
def test_random_trees_match_raw_margin(seed):
    trees = random_trees.make({**SMALL, "seed": seed})
    x = random_trees.rows(trees, {}, np.random.default_rng(seed), 700, "bins")
    want = artifact.to_ensemble(trees).raw_margin(x)
    got = reference.margins(trees, x)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_random_trees_leaves_are_not_bfloat16():
    import ml_dtypes

    trees = random_trees.make({**SMALL, "seed": 3, "n_trees": 200})
    leaves = trees["value"][trees["feature"] < 0]
    assert not np.any(leaves.astype(ml_dtypes.bfloat16).astype(np.float32) == leaves)


@pytest.fixture(scope="module")
def gas_small():
    return gbdt_tabular.make({"model_file": "chipbench/models/gas-gbdt.npz", "n_rounds": 2})


def test_trained_gbdt_matches_raw_margin_on_bins_and_floats(gas_small):
    from repro.core.quantize import FeatureQuantizer

    trees = dict(gas_small)
    data = {"edges": trees.pop("edges"), "pool": trees.pop("pool")}
    ens = artifact.to_ensemble(trees)
    rng = np.random.default_rng(4)
    xf = gbdt_tabular.rows(trees, data, rng, 500, "floats")
    xb = FeatureQuantizer(edges=data["edges"], n_bins=256).transform(xf)
    np.testing.assert_array_equal(xb, gbdt_tabular.bin_rows(xf, data["edges"]))
    want = ens.raw_margin(xb)
    np.testing.assert_allclose(reference.margins(trees, xb), want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(reference.margins(trees, xf, edges=data["edges"], floats=True),
                               want, rtol=1e-6, atol=1e-6)


def test_gas_model_file_holds_the_configured_model():
    """The committed model is the one the configuration describes, and
    the maker keeps the first rounds of it whole."""
    from chipbench import spec, work

    cfg = json.loads((spec.ROOT / "chipbench" / "configs" / "gas-gbdt.json").read_text())
    full = gbdt_tabular.make(cfg)
    assert full["feature"].shape[0] == cfg["n_rounds"] * full["n_outputs"] == 360
    assert work.sizes_of(full).leaves == 22_336
    assert full["n_features"] == 129 and full["n_outputs"] == 6
    assert len(full["edges"]) == 129 and full["pool"].shape[1] == 129
    few = gbdt_tabular.make({**cfg, "n_rounds": 3})
    np.testing.assert_array_equal(few["value"], full["value"][:18])
    assert list(few["tree_out"]) == list(range(6)) * 3
    with pytest.raises(ValueError, match="rounds"):
        gbdt_tabular.make({**cfg, "n_rounds": 61})


def test_high_precision_control_rounds_every_leaf():
    trees = random_trees.make({**SMALL, "seed": 5})
    x = random_trees.rows(trees, {}, np.random.default_rng(5), 300, "bins")
    exact = reference.margins(trees, x)
    high = reference.margins(trees, x, precision="high")
    gap = np.abs(high - exact).max()
    assert 0 < gap < 1e-4 * np.abs(exact).max()
