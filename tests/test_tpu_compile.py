"""The main path's kernels compile for a TPU v5e — no chip needed.

The TPU compiler is installed with jaxlib and compiles for a described,
unattached topology, so these tests catch what interpret mode cannot:
block shapes off the (8, 128) tiling, SMEM/VMEM over budget, layouts
Mosaic refuses.  Widths are phase a of ``chip_smoke.py`` (the paper's
4096-tree ensemble: 1,048,576 CAM rows, 130 features padded to 256,
8 classes padded to 8, a 1024-row batch).

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
from repro.kernels.cam_match import cam_match_pallas
from oracles import random_cam_table

R, F_PAD, C_PAD, B = 1 << 20, 256, 8, 1024
R_BLK, F_BLK = 256, 128


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
    "mode,dtype",
    [("inclusive", jnp.uint8), ("direct", jnp.int32), ("soft", jnp.float32)],
)
def test_kernel_compiles_for_one_v5e_chip(topo, mode, dtype):
    one = SingleDeviceSharding(topo.devices[0])
    args = (
        _spec((B, F_PAD), dtype, one),  # queries
        _spec((R, F_PAD), dtype, one),  # CAM low
        _spec((R, F_PAD), dtype, one),  # CAM high
        _spec((R, C_PAD), jnp.float32, one),  # leaf matrix
        _spec((R // R_BLK, F_PAD // F_BLK), jnp.int32, one),  # tile mask
        _spec((1, C_PAD), jnp.float32, one),  # fused bias
    )
    compiled = jax.jit(
        lambda *a: cam_match_pallas(*a, mode=mode, interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


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
    q_sh, row_sh = in_sh[0], in_sh[1]
    assert isinstance(row_sh, NamedSharding)
    args = (
        _spec((B, F_PAD), jnp.uint8, q_sh),
        _spec((R, F_PAD), jnp.uint8, row_sh),
        _spec((R, F_PAD), jnp.uint8, row_sh),
        _spec((R, C_PAD), jnp.float32, row_sh),
        _spec((R // R_BLK, F_PAD // F_BLK), jnp.int32, row_sh),
    )
    compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(
        *args
    ).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert "all-reduce" in hlo
