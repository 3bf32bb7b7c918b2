"""The main path's kernels compile for a TPU v5e — no chip needed.

The TPU compiler is installed with jaxlib and compiles for a described,
unattached topology, so these tests catch what interpret mode cannot:
block shapes off the (8, 128) tiling, SMEM/VMEM over budget, layouts
Mosaic refuses.  Widths are phase a of ``chip_smoke.py`` (the paper's
4096-tree ensemble: 1,048,576 CAM rows, 130 features padded on sublanes,
8 classes padded to 8, a 1024-row batch) and the gas GBDT's 129
features; the bound tables are feature-major, ``(F_pad, R)``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and under pytest-xdist every
worker imports this file.  The persistent compilation cache is off
around these compiles (an entry compiled for a described chip cannot be
read back without one).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.core.deploy import DeployConfig
from repro.core.engine import XTimeEngine
from repro.kernels.cam_match import cam_match_pallas, n_groups, sublane_rows
from oracles import random_cam_table

R, C_PAD, B = 1 << 20, 8, 1024
R_BLK, F_BLK = 256, 16


def _f_pad(f, dtype):
    return -(-f // sublane_rows(dtype)) * sublane_rows(dtype)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize(
    "mode,dtype,f",
    [
        ("inclusive", jnp.uint8, 130),
        ("direct", jnp.int32, 130),
        ("soft", jnp.float32, 130),
        ("inclusive", jnp.uint8, 129),  # the gas GBDT's width
    ],
)
def test_kernel_compiles_for_one_v5e_chip(topo, mode, dtype, f):
    one = SingleDeviceSharding(topo.devices[0])
    f_pad = _f_pad(f, dtype)
    args = (
        _spec((B, f_pad), dtype, one),  # queries
        _spec((f_pad, R), dtype, one),  # CAM low, feature-major
        _spec((f_pad, R), dtype, one),  # CAM high
        _spec((R, C_PAD), jnp.float32, one),  # leaf matrix
        _spec((R // R_BLK, n_groups(f, F_BLK)), jnp.int32, one),  # tile mask
        _spec((1, C_PAD), jnp.float32, one),  # fused bias
    )
    compiled = jax.jit(
        lambda *a: cam_match_pallas(*a, mode=mode, interpret=False, n_feat=f)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_compiled_kernel_rejects_rows_off_the_lanes():
    """Compiled, CAM rows lie on the lanes: an r_blk that is no multiple
    of 128 is refused with the reason, before Mosaic sees it."""
    q = jnp.zeros((8, 32), jnp.uint8)
    t = jnp.zeros((32, 192), jnp.uint8)
    leaf = jnp.zeros((192, 8), jnp.float32)
    with pytest.raises(ValueError, match="multiple of 128"):
        cam_match_pallas(q, t, t, leaf, b_blk=8, r_blk=64, mode="inclusive",
                         interpret=False)


def test_row_sharded_accumulate_compiles_for_four_v5e_chips(topo):
    """The engine's own 'accumulate' program — shard_map over the row
    axis, the kernel per shard, a psum — for a (1, 4) v5e mesh."""
    mesh = Mesh(
        np.asarray(topo.devices).reshape(1, 4), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
    table = random_cam_table(np.random.default_rng(0), r=64, f=130,
                             n_outputs=8)
    # bound against the described mesh like any mesh engine, minus the
    # array placement a described device cannot take
    eng = XTimeEngine.from_config(
        table, DeployConfig(backend="pallas", interpret=False,
                            noc_config="accumulate"),
        mesh=mesh, place=False,
    )
    assert eng.spmd == "shard_map" and not eng.fuse_epilogue
    assert eng.arrays.r_pad % (4 * eng.r_blk) == 0
    fn, in_sh, out_sh = eng.serve_step_for_dryrun()
    q_sh, low_sh, leaf_sh = in_sh[0], in_sh[1], in_sh[3]
    assert isinstance(low_sh, NamedSharding)
    # the feature-major bounds shard on their row axis, axis 1
    assert tuple(low_sh.spec) == (None, "model")
    assert tuple(leaf_sh.spec) == ("model",)
    f_pad = eng.arrays.f_pad
    args = (
        _spec((B, f_pad), jnp.uint8, q_sh),
        _spec((f_pad, R), jnp.uint8, low_sh),
        _spec((f_pad, R), jnp.uint8, in_sh[2]),
        _spec((R, C_PAD), jnp.float32, leaf_sh),
        _spec((R // R_BLK, n_groups(130, F_BLK)), jnp.int32, in_sh[4]),
    )
    compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(
        *args
    ).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert "all-reduce" in hlo
