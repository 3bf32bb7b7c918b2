"""Kernel v2 (DESIGN.md §10): compact-dtype packing, feature-grid tiling,
wildcard row ordering, interpret resolution and autotune persistence.

The non-negotiable contract: the packed uint8/uint16 paths are BIT-EQUAL
to the v1 int32 oracle across every cell mode, including bin values at
the dtype boundaries (0, 255/65535) and wildcard sentinel rows — on a
single device here and under shard_map in tests/test_scaleout-style
subprocess harnesses below.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st
from oracles import assert_packed_reencoding_bit_equal, random_tables

from repro.core.compile import (
    compile_ensemble,
    order_rows_by_wildcards,
    select_table_dtype,
)
from repro.core.deploy import DeployConfig
from repro.core.engine import XTimeEngine, resolve_table_dtype
from repro.core.trees import GBDTParams, train_gbdt
from repro.kernels import ops as kops

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# -- dtype selection -----------------------------------------------------------


def test_select_table_dtype_thresholds():
    assert select_table_dtype(2) == "uint8"
    assert select_table_dtype(256) == "uint8"
    assert select_table_dtype(257) == "uint16"
    assert select_table_dtype(1 << 16) == "uint16"
    assert select_table_dtype((1 << 16) + 1) == "int32"


def test_compile_records_table_dtype():
    rng = np.random.default_rng(0)
    xb = rng.integers(0, 16, size=(64, 4))
    y = (xb.sum(1) > 30).astype(np.int64)
    ens = train_gbdt(xb, y, task="binary", n_bins=16,
                     params=GBDTParams(n_rounds=2, max_leaves=4))
    assert compile_ensemble(ens).table_dtype == "uint8"
    assert compile_ensemble(ens, table_dtype="int32").table_dtype == "int32"
    with pytest.raises(ValueError):
        compile_ensemble(ens, table_dtype="float32")


def test_faithful_modes_pin_int32():
    rng = np.random.default_rng(0)
    xb = rng.integers(0, 16, size=(64, 4))
    y = (xb.sum(1) > 30).astype(np.int64)
    ens = train_gbdt(xb, y, task="binary", n_bins=16,
                     params=GBDTParams(n_rounds=2, max_leaves=4))
    table = compile_ensemble(ens)
    assert table.table_dtype == "uint8"
    for mode in ("msb_lsb", "two_cycle"):
        cfg = DeployConfig(mode=mode)
        assert resolve_table_dtype(table, cfg) == "int32"
        with pytest.raises(ValueError):
            DeployConfig(mode=mode, table_dtype="uint8")


# -- packed-kernel bit-equivalence vs the v1 int32 oracle ----------------------
# (the generators and the differential assertion live in tests/oracles.py,
# shared with test_kernel_compact.py and test_kernel_v3.py)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_uint8_packed_bit_equals_int32_oracle(seed):
    """Property: uint8 inclusive packing is a re-encoding of the int32
    exclusive tables — identical bits out, jnp and Pallas, boundary bins
    0/255 and wildcard rows included."""
    for backend in ("jnp", "pallas"):
        assert_packed_reencoding_bit_equal(seed, 256, "uint8", "direct", backend)


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_uint16_packed_bit_equals_int32_oracle(seed):
    """Same property on a 16-bit grid (boundary bin 65535)."""
    assert_packed_reencoding_bit_equal(seed, 1 << 16, "uint16", "direct", "jnp")


def test_uint16_pallas_spot():
    assert_packed_reencoding_bit_equal(7, 1 << 16, "uint16", "direct", "pallas")


def test_packed_overflow_rejected():
    rng = np.random.default_rng(0)
    low, high = random_tables(rng, 8, 4, 4096)
    leaf = np.zeros((8, 1), dtype=np.float32)
    with pytest.raises(ValueError):
        kops.pack_tables(low, high, leaf, n_bins=4096, dtype="uint8")


@pytest.mark.parametrize("mode", ["direct", "inclusive", "msb_lsb", "two_cycle"])
def test_engine_all_modes_bit_equal_across_dtypes(mode):
    """Engine-level: every cell mode × admissible table dtype produces the
    exact same margins (the kernel-v2 equivalence contract)."""
    rng = np.random.default_rng(3)
    xb = rng.integers(0, 256, size=(200, 9))
    y = (xb[:, 0].astype(np.int64) * 3 + xb[:, 4] > 500).astype(np.int64)
    ens = train_gbdt(xb, y, task="binary", n_bins=256,
                     params=GBDTParams(n_rounds=4, max_leaves=16))
    table = compile_ensemble(ens)
    ref = None
    dtypes = ("int32",) if mode in ("msb_lsb", "two_cycle") else (
        "int32", "uint8", "uint16",
    )
    for backend in ("jnp", "pallas"):
        backend_ref = None  # packing is bit-exact within one backend
        for td in dtypes:
            eng = XTimeEngine.from_config(
                table,
                DeployConfig(backend=backend, mode=mode, table_dtype=td,
                             b_blk=64, r_blk=64),
            )
            m = np.asarray(eng.raw_margin(xb))
            if backend_ref is None:
                backend_ref = m
            np.testing.assert_array_equal(m, backend_ref)
            if ref is None:
                ref = m
            # across backends the tiled accumulation may reassociate the
            # float32 sums — semantics identical, bits within 1 ULP
            np.testing.assert_allclose(m, ref, rtol=1e-6, atol=1e-7)


# -- wildcard tile mask + row ordering ----------------------------------------


def test_tile_mask_marks_wildcard_tiles():
    n_bins = 256
    low = np.zeros((64, 256), dtype=np.int32)
    high = np.full((64, 256), n_bins, dtype=np.int32)
    low[:32, 0] = 3  # first row block constrains feature tile 0 only
    high[:32, 0] = 7
    lo_p, hi_p, lm, incl = kops.pack_tables(
        low, high, np.zeros((64, 8), np.float32),
        r_blk=32, n_bins=n_bins, dtype="uint8",
    )
    mask = kops.wildcard_tile_mask(
        lo_p, hi_p, r_blk=32, f_blk=128, n_bins=n_bins, inclusive=incl,
    )
    np.testing.assert_array_equal(mask, [[1, 0], [0, 0]])


def test_row_ordering_increases_skippable_tiles_and_preserves_bits():
    """Interleaved rows that constrain alternating feature tiles: unordered
    they poison every (row, feature) tile; ordered, half the tiles become
    skippable — with identical predictions."""
    rng = np.random.default_rng(5)
    xb = rng.integers(0, 256, size=(300, 300))
    y = (xb[:, 0] > 127).astype(np.int64)
    ens = train_gbdt(xb, y, task="binary", n_bins=256,
                     params=GBDTParams(n_rounds=4, max_leaves=8))
    unordered = compile_ensemble(ens, order_rows=False)
    ordered = order_rows_by_wildcards(unordered)
    assert (
        ordered.tile_skip_fraction(64, 128)
        >= unordered.tile_skip_fraction(64, 128)
    )
    m0 = np.asarray(
        XTimeEngine.from_config(unordered, DeployConfig()).raw_margin(xb[:64])
    )
    m1 = np.asarray(
        XTimeEngine.from_config(ordered, DeployConfig()).raw_margin(xb[:64])
    )
    np.testing.assert_array_equal(m0, m1)


def test_engine_mask_actually_skips_and_stays_correct():
    """A pallas engine on a table whose constraints live entirely in the
    first feature group must skip every other group's compares — and
    still agree with the jnp oracle to the last bit."""
    from repro.core.compile import CAMTable

    rng = np.random.default_rng(6)
    R, F, n_bins = 64, 200, 256
    low = np.zeros((R, F), dtype=np.int32)
    high = np.full((R, F), n_bins, dtype=np.int32)
    low[:, :16] = rng.integers(0, 128, size=(R, 16))
    high[:, :16] = low[:, :16] + rng.integers(1, 128, size=(R, 16))
    table = CAMTable(
        low=low, high=high,
        leaf=rng.normal(size=R).astype(np.float32),
        tree_id=np.arange(R, dtype=np.int32),
        class_id=(np.arange(R) % 2).astype(np.int32),
        n_trees=R, n_features=F, n_bins=n_bins, n_outputs=2,
        task="multiclass", kind="gbdt", base_score=0.0, n_classes=2,
        table_dtype="uint8",
    )
    eng = XTimeEngine.from_config(
        table, DeployConfig(backend="pallas", b_blk=32, r_blk=32),
    )
    mask = np.asarray(eng.arrays.tile_mask)
    assert mask.shape == (2, 13)  # 200 features in groups of 16
    np.testing.assert_array_equal(mask[:, 0], 1)
    np.testing.assert_array_equal(mask[:, 1:], 0)  # all wildcards
    assert eng.mask_active_share == 2 / 26
    xq = rng.integers(0, n_bins, size=(96, F))
    ref = np.asarray(
        XTimeEngine.from_config(
            table, DeployConfig(backend="jnp", table_dtype="int32",
                                b_blk=32, r_blk=32)
        ).raw_margin(xq)
    )
    np.testing.assert_allclose(
        np.asarray(eng.raw_margin(xq)), ref, rtol=1e-6, atol=1e-7
    )


def test_out_of_range_queries_rejected_not_wrapped():
    """The v1 int32 compare was accidentally lenient with out-of-grid bins
    (value >= high never matches); a packed engine must REJECT them — a
    uint8 cast would wrap 300 to 44 and match rows it must not."""
    rng = np.random.default_rng(8)
    xb = rng.integers(0, 256, size=(128, 5))
    y = (xb[:, 0] > 127).astype(np.int64)
    ens = train_gbdt(xb, y, task="binary", n_bins=256,
                     params=GBDTParams(n_rounds=2, max_leaves=8))
    eng = XTimeEngine.from_config(compile_ensemble(ens), DeployConfig())
    assert eng.table_dtype == "uint8"
    bad = xb.copy()
    bad[0, 0] = 300
    with pytest.raises(ValueError, match="do not fit table dtype"):
        eng.raw_margin(bad)
    with pytest.raises(ValueError, match="do not fit table dtype"):
        kops.pad_to_bucket(bad, 128, eng.arrays.f_pad, dtype="uint8")
    eng.raw_margin(xb)  # in-range bins unaffected


def test_defect_injected_table_falls_back_to_int32():
    """Defect flips can push bounds outside the packed encoding (low to
    n_bins, high below low); the perturbed table must drop to the int32
    layout and still bind an engine (the serving hot-swap defect study)."""
    from repro.core.defects import inject_table_defects

    rng = np.random.default_rng(9)
    xb = rng.integers(0, 256, size=(200, 6))
    y = (xb[:, 1] > 127).astype(np.int64)
    ens = train_gbdt(xb, y, task="binary", n_bins=256,
                     params=GBDTParams(n_rounds=3, max_leaves=8))
    table = compile_ensemble(ens)
    assert table.table_dtype == "uint8"
    bad = inject_table_defects(table, 0.1, np.random.default_rng(0))
    assert bad.table_dtype == "int32"
    eng = XTimeEngine.from_config(bad, DeployConfig())  # must not raise
    assert eng.table_dtype == "int32"
    eng.raw_margin(xb[:32])
    # an explicit packed override on an out-of-range table fails loudly
    if int(bad.low.max()) > 255 or int(bad.high.min()) < 1:
        with pytest.raises(ValueError):
            XTimeEngine.from_config(bad, DeployConfig(table_dtype="uint8"))


# -- interpret resolution ------------------------------------------------------


def test_interpret_auto_resolves_per_platform():
    assert DeployConfig().interpret == "auto"
    with pytest.raises(ValueError):
        DeployConfig(interpret="yes")
    rng = np.random.default_rng(0)
    xb = rng.integers(0, 16, size=(64, 4))
    y = (xb.sum(1) > 30).astype(np.int64)
    ens = train_gbdt(xb, y, task="binary", n_bins=16,
                     params=GBDTParams(n_rounds=2, max_leaves=4))
    table = compile_ensemble(ens)
    eng = XTimeEngine.from_config(table, DeployConfig())
    # the suite pins JAX_PLATFORMS=cpu, so 'auto' must resolve to the
    # interpreter (False only ever happens on real TPU)
    assert eng.interpret is True
    assert XTimeEngine.from_config(
        table, DeployConfig(interpret=False)
    ).interpret is False


# -- shard_map packed equivalence (8 fake devices, subprocess) -----------------

_SHARD_CODE = """
import json
import numpy as np
from repro.api import build
from repro.core.deploy import DeployConfig
from repro.core.trees import GBDTParams, train_gbdt
from repro.launch.mesh import make_host_mesh

rng = np.random.default_rng(0)
xb = rng.integers(0, 256, size=(256, 12))
y = (xb[:, 0].astype(np.int64) + xb[:, 5] > 250).astype(np.int64)
ens = train_gbdt(xb, y, task="binary", n_bins=256,
                 params=GBDTParams(n_rounds=5, max_leaves=16))
cm = build(ens)
assert cm.table.table_dtype == "uint8"
ref = np.asarray(
    cm.engine(**{"table_dtype": "int32", "mode": "direct"}).raw_margin(xb)
)
mesh = make_host_mesh()
out = {}
for mode in ("direct", "inclusive", "msb_lsb", "two_cycle"):
    for td in ("auto", "int32"):
        eng = cm.engine(mesh=mesh, mode=mode, table_dtype=td)
        m = np.asarray(eng.raw_margin(xb))
        out[f"{mode}/{td}"] = {
            "spmd": eng.spmd,
            "dtype": eng.table_dtype,
            "bit_equal": bool(np.array_equal(m, ref)),
            "max_err": float(np.abs(m - ref).max()),
        }
print(json.dumps(out))
"""


def test_packed_paths_bit_equal_under_shard_map():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", _SHARD_CODE], capture_output=True, text=True,
        env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    results = json.loads(out.stdout.strip().splitlines()[-1])
    assert results["direct/auto"]["dtype"] == "uint8"
    assert results["msb_lsb/auto"]["dtype"] == "int32"
    for key, res in results.items():
        assert res["spmd"] == "shard_map", (key, res)
        # psum reduction reordering allows <= 1 ULP vs single device; the
        # packed re-encoding itself must not add ANY error on top
        assert res["bit_equal"] or res["max_err"] < 1e-5, (key, res)
    # packed and int32 agree bitwise WITH EACH OTHER under shard_map
    for mode in ("direct", "inclusive"):
        a, b = results[f"{mode}/auto"], results[f"{mode}/int32"]
        assert a["max_err"] == b["max_err"], (mode, a, b)
