"""Shared differential-oracle harness for the CAM kernels (kernel v3).

Every kernel version — v1 int32 exclusive-high, v2 packed inclusive-high,
and the v3 variants (fused epilogue, column clustering, dispatch-selected
configurations) — is gated by the same two references:

  * the SAME-BACKEND v1 int32 engine with the fused epilogue disabled.
    A packed / permuted / fused engine is a re-encoding of the identical
    computation at the same tile sizes, so the float32 reduction order
    matches and the margins must be BIT-EQUAL;
  * the plain jnp reference (``cam_match_ref`` via a jnp engine).  A
    different backend may reassociate the tiled float32 sums, so
    agreement is within 1 ULP (``rtol=1e-6, atol=1e-7``).

``XTIME_TEST_INTERPRET`` selects how the Pallas kernel runs under test:
``auto`` (default) resolves per platform exactly like production, ``1``
pins ``interpret=True``.  CI runs the harness under both settings.

This module lives on the tests path (imported bare, like
``_hypothesis_compat``); it holds shared fixtures, assertions and the
8-fake-device subprocess runner only — no test functions.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np

from repro.core.compile import CAMTable
from repro.core.deploy import DeployConfig
from repro.core.engine import XTimeEngine
from repro.kernels import ops as kops
from repro.kernels.ref import cam_match_ref


def env_interpret() -> bool | str:
    """Interpret setting for engine-level tests: 'auto' or True.

    Driven by ``XTIME_TEST_INTERPRET`` so CI can force the interpreter
    explicitly ('1') and separately exercise the production 'auto'
    resolution path.
    """
    return True if os.environ.get("XTIME_TEST_INTERPRET", "auto") == "1" else "auto"


def env_interpret_kernel() -> bool | None:
    """Interpret setting for direct ``cam_match`` calls: True or None
    (None defers to the kernel's per-platform resolution)."""
    return True if os.environ.get("XTIME_TEST_INTERPRET", "auto") == "1" else None


def run_on_8_devices(code: str) -> dict:
    """Run ``code`` in a subprocess on 8 fake CPU devices and return the
    JSON object it prints last (the test process itself has one device).

    The platform is pinned to the CPU: fake host devices need it anyway,
    and an unset platform makes jax probe the TPU plugin, which stalls
    on the (absent) cloud metadata server in sandboxed environments.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# -- table generators ----------------------------------------------------------


def random_tables(rng, r, f, n_bins, *, edge_bias=0.3, wildcard=0.3):
    """Exclusive-high int32 tables with wildcard rows and dtype-boundary
    bin values (0 and n_bins-1 appear both as thresholds and queries)."""
    low = rng.integers(0, n_bins, size=(r, f)).astype(np.int32)
    high = np.minimum(low + rng.integers(1, n_bins, size=(r, f)), n_bins)
    high = high.astype(np.int32)
    # force dtype-boundary cells: [0, 1) at the bottom, [n_bins-1, n_bins)
    # at the top of the grid
    edge = rng.random((r, f)) < edge_bias
    lo_edge = rng.random((r, f)) < 0.5
    low[edge & lo_edge], high[edge & lo_edge] = 0, 1
    low[edge & ~lo_edge], high[edge & ~lo_edge] = n_bins - 1, n_bins
    dc = rng.random((r, f)) < wildcard
    low[dc], high[dc] = 0, n_bins
    # whole-row wildcard sentinels (ingest bias rows)
    low[: max(1, r // 16)] = 0
    high[: max(1, r // 16)] = n_bins
    return low, high


def compact_problem(rng, b, r, f, c):
    """Pre-packed inclusive uint8 tables + queries (kernel-native form)."""
    low = rng.integers(0, 256, size=(r, f)).astype(np.uint8)
    width = rng.integers(0, 256, size=(r, f))
    high = np.minimum(low.astype(np.int64) + width, 255).astype(np.uint8)
    dc = rng.random((r, f)) < 0.3  # always-match cells
    low[dc], high[dc] = 0, 255
    # never-match padding rows: low=1 > high=0
    low[-3:], high[-3:] = 1, 0
    leaf = rng.normal(size=(r, c)).astype(np.float32)
    leaf[-3:] = 0.0
    q = rng.integers(0, 256, size=(b, f)).astype(np.uint8)
    return q, low, high, leaf


def random_cam_table(rng, *, r=64, f=20, n_bins=256, n_outputs=2) -> CAMTable:
    """A standalone CAMTable over :func:`random_tables` bounds, for
    engine-level oracle checks without training an ensemble."""
    low, high = random_tables(rng, r, f, n_bins)
    return CAMTable(
        low=low, high=high,
        leaf=rng.normal(size=r).astype(np.float32),
        tree_id=np.arange(r, dtype=np.int32),
        class_id=(np.arange(r) % n_outputs).astype(np.int32),
        n_trees=r, n_features=f, n_bins=n_bins, n_outputs=n_outputs,
        task="multiclass" if n_outputs > 1 else "regression",
        kind="gbdt", base_score=0.25, n_classes=n_outputs,
        table_dtype="uint8" if n_bins <= 256 else "uint16",
    )


# -- kernel-level differential runs --------------------------------------------


def run_encoding(q, low, high, leaf, *, n_bins, dtype, mode, backend, b, c):
    """One cam_match evaluation in the given table encoding/backend."""
    lo_p, hi_p, lm, incl = kops.pack_tables(
        low, high, leaf, r_blk=32, n_bins=n_bins, dtype=dtype,
    )
    assert incl == (np.dtype(dtype).kind == "u")
    mask = kops.wildcard_tile_mask(
        lo_p, hi_p, r_blk=32, f_blk=8, n_bins=n_bins, inclusive=incl,
    )
    kernel_mode = "inclusive" if incl else mode
    qp = kops.pad_queries(jnp.asarray(q), lo_p.shape[0], b_blk=32, dtype=dtype)
    if backend == "pallas":
        out = kops.cam_match(
            qp, jnp.asarray(lo_p), jnp.asarray(hi_p), jnp.asarray(lm),
            jnp.asarray(mask), out_b=b, out_c=c, b_blk=32, r_blk=32, f_blk=8,
            mode=kernel_mode, interpret=env_interpret_kernel(),
        )
    else:
        out = cam_match_ref(  # the reference reads row-major tables
            qp, jnp.asarray(lo_p.T), jnp.asarray(hi_p.T), jnp.asarray(lm),
            mode=kernel_mode,
        )[:b, :c]
    return np.asarray(out)


def assert_packed_reencoding_bit_equal(seed, n_bins, dtype, mode, backend):
    """Packed tables are a RE-ENCODING of the v1 int32 layout: identical
    bits out when only the encoding differs (same shapes, same backend,
    hence the same float reduction order)."""
    rng = np.random.default_rng(seed)
    b, r, f, c = 32, 96, 11, 3
    low, high = random_tables(rng, r, f, n_bins)
    leaf = rng.normal(size=(r, c)).astype(np.float32)
    q = rng.integers(0, n_bins, size=(b, f)).astype(np.int32)
    # boundary queries
    q[:4] = 0
    q[4:8] = n_bins - 1

    kw = dict(n_bins=n_bins, mode=mode, backend=backend, b=b, c=c)
    oracle = run_encoding(q, low, high, leaf, dtype="int32", **kw)
    packed = run_encoding(q, low, high, leaf, dtype=dtype, **kw)
    np.testing.assert_array_equal(packed, oracle)
    # and the match SEMANTICS (not just the float sums) agree with the
    # plain unpadded reference within float32 reassociation
    ref = np.asarray(
        cam_match_ref(jnp.asarray(q), jnp.asarray(low), jnp.asarray(high),
                      jnp.asarray(leaf), mode="direct")
    )
    np.testing.assert_allclose(packed, ref, rtol=1e-5, atol=1e-6)


# -- the engine-level oracle gate ---------------------------------------------


def assert_bit_equal_to_oracle(
    table: CAMTable,
    queries: np.ndarray,
    deploy: DeployConfig,
) -> np.ndarray:
    """The shared differential-oracle gate every kernel version must pass.

    Binds ``deploy`` on ``table`` and asserts its margins are

      1. BIT-EQUAL to the same-backend engine on the mode's CANONICAL
         table layout with the fused epilogue off (the registry's pinned
         ``table_dtype_policy``, int32 for the hard modes — same tile
         sizes → identical float32 reduction order), and
      2. within 1 ULP of the jnp reference engine (mode='soft' compares
         against the jnp soft engine at the SAME tau; every hard mode
         against the jnp 'direct' int32 engine).

    Returns the candidate margins for further assertions.
    """
    from repro.core.precision import get_cell_mode

    candidate = XTimeEngine.from_config(table, deploy)
    m = np.asarray(candidate.raw_margin(queries))

    policy = get_cell_mode(deploy.mode).table_dtype_policy
    v1 = XTimeEngine.from_config(
        table,
        deploy.replace(table_dtype=policy or "int32", fuse_epilogue=False),
    )
    np.testing.assert_array_equal(m, np.asarray(v1.raw_margin(queries)))

    if get_cell_mode(deploy.mode).soft:
        ref_cfg = DeployConfig(
            backend="jnp", mode="soft", tau=deploy.tau,
            table_dtype="float32",
            b_blk=deploy.b_blk, r_blk=deploy.r_blk, f_blk=deploy.f_blk,
        )
    else:
        ref_cfg = DeployConfig(
            backend="jnp", mode="direct", table_dtype="int32",
            b_blk=deploy.b_blk, r_blk=deploy.r_blk, f_blk=deploy.f_blk,
        )
    ref = XTimeEngine.from_config(table, ref_cfg)
    np.testing.assert_allclose(
        m, np.asarray(ref.raw_margin(queries)), rtol=1e-6, atol=1e-7,
    )
    return m
