"""The model's work is counted from the ensemble, whatever the table."""

import numpy as np
import pytest

from chipbench import artifact, work
from chipbench.makers import random_trees

CFG = {"n_trees": 16, "depth": 4, "n_features": 20, "n_bins": 256, "n_classes": 4,
       "task": "multiclass", "p_dup": 0.0, "leaf_std": 0.1, "seed": 7}


@pytest.fixture(scope="module")
def trees():
    return random_trees.make(CFG)


@pytest.mark.parametrize("compress", ["off", "prune", "merge", "full"])
@pytest.mark.parametrize("table_dtype", ["auto", "int32"])
@pytest.mark.parametrize("r_blk", [256, 512])
def test_same_work_under_every_table(trees, compress, table_dtype, r_blk):
    from repro.api import build
    from repro.core.deploy import DeployConfig

    cm = build(artifact.to_ensemble(trees), compress=compress,
               deploy=DeployConfig(backend="pallas", r_blk=r_blk, table_dtype=table_dtype))
    eng = cm.engine()
    assert eng.arrays.r_pad % r_blk == 0  # the table is padded, the work is not
    s = work.sizes_of(trees)
    leaves = 16 * 2 ** 4
    assert (s.leaves, s.features, s.outputs, s.bin_bytes) == (leaves, 20, 4, 1)
    assert s.ops_per_row == leaves * (2 * 20 + 2 * 4)
    assert s.bytes_per_call(100) == leaves * 20 * 2 + leaves * 4 * 4 + 100 * 20 + 100 * 4 * 4


def test_paper_chunk_is_compute_bound():
    peaks = work.peaks_for("TPU v5 lite")
    s = work.ModelSizes(leaves=1_048_576, features=130, outputs=8, n_bins=256)
    assert s.ops_per_row == 289_406_976
    ops, nbytes = 8192 * s.ops_per_row, s.bytes_per_call(8192)
    assert ops / nbytes > work.ridge_ops_per_byte(peaks)
    assert work.least_time_s(s, [8192], peaks) == pytest.approx(ops / peaks["int8_ops_per_s"])


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        work.peaks_for("cpu")


def test_gbdt_leaves_skip_padding():
    t = {"feature": np.array([[0, -1, -1, -1, -1], [0, 1, -1, -1, -1]]),
         "node_count": np.array([3, 5]), "n_features": 2, "n_outputs": 1, "n_bins": 16}
    assert work.sizes_of(t).leaves == 2 + 3
