"""cam_match kernel micro-benchmarks (kernel v2, DESIGN.md §10).

Times the engine's actual compute paths across CAM table sizes and
table dtypes on the platform the bench runs on:

  * ``v1_int32``   — the v1 layout: int32 exclusive-high tables, direct
    compare (the baseline the packed paths must beat);
  * ``v2_uint8``   — compact inclusive-high uint8 tables (the paper's
    native 8-bit precision), native-dtype compare — 4x less table
    traffic for identical bits;
  * ``v2_pallas``  — the tiled v2 Pallas kernel on uint8 tables with the
    wildcard tile mask (interpret mode off-TPU, so its timing is only
    meaningful on TPU; kept small and recorded for trend, not gated);
  * ``v3_dispatch`` — what the kernel-v3 measured-cost dispatch table
    (``repro.core.tune.TunePlan.dispatch``) binds at each size: the
    faster of the v1/v2 candidates above.  This is the gated row — the
    crossover is shape-dependent (v2 loses at b256/r4096/f32, wins at
    r16384/f130), and dispatch must never be slower than v1.

Every row's ``derived`` carries the traffic-model numbers
(``repro.core.perfmodel.kernel_traffic_model``) plus, for packed rows,
the measured ``speedup_vs_int32`` — the committed BENCH entry that
demonstrates the v1 -> v2 delta.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import budget, time_call
from repro.core.perfmodel import kernel_traffic_model
from repro.core.tune import kernel_version
from repro.kernels import ops as kops
from repro.kernels.ref import cam_match_ref

# (batch, rows, features, channels) problem sizes; the last grows with
# BENCH_FAST=0 to the roofline regime
_SIZES = [
    (256, 4096, 32, 8),
    (256, 16384, 130, 8),
]


def _problem(rng, b, r, f, c):
    """Random CAM problem in BOTH encodings: exclusive int32 + packed uint8."""
    low = rng.integers(0, 256, size=(r, f)).astype(np.int32)
    width = rng.integers(1, 256, size=(r, f))
    high = np.minimum(low + width, 256).astype(np.int32)
    dc = rng.random((r, f)) < 0.3  # wildcard cells
    low[dc], high[dc] = 0, 256
    leaf = rng.normal(size=(r, c)).astype(np.float32)
    q = rng.integers(0, 256, size=(b, f)).astype(np.int32)
    lo8 = low.astype(np.uint8)
    hi8 = (high - 1).astype(np.uint8)  # inclusive packed form
    q8 = q.astype(np.uint8)
    return q, low, high, leaf, q8, lo8, hi8


def run() -> list[dict]:
    rows = []
    rng = np.random.default_rng(1)
    sizes = _SIZES + [(budget(1024, 256), budget(65536, 16384), 130, 8)]
    sizes = list(dict.fromkeys(sizes))  # FAST budgets can collide with _SIZES
    for (b, r, f, c) in sizes:
        q, low, high, leaf, q8, lo8, hi8 = _problem(rng, b, r, f, c)
        la = jnp.asarray(leaf)

        fn32 = jax.jit(lambda qq, lo, hi: cam_match_ref(qq, lo, hi, la, mode="direct"))
        fn8 = jax.jit(
            lambda qq, lo, hi: cam_match_ref(qq, lo, hi, la, mode="inclusive")
        )
        a32 = (jnp.asarray(q), jnp.asarray(low), jnp.asarray(high))
        a8 = (jnp.asarray(q8), jnp.asarray(lo8), jnp.asarray(hi8))
        # the packed path must be a *re-encoding*, not a re-definition
        np.testing.assert_allclose(
            np.asarray(fn32(*a32)), np.asarray(fn8(*a8)), rtol=1e-5, atol=1e-5
        )

        us32 = time_call(lambda: fn32(*a32).block_until_ready())
        us8 = time_call(lambda: fn8(*a8).block_until_ready())
        t32 = kernel_traffic_model(
            batch=b, rows=r, features=f, channels=c, table_dtype="int32"
        )
        t8 = kernel_traffic_model(
            batch=b, rows=r, features=f, channels=c, table_dtype="uint8"
        )
        cfg = {"b": b, "r": r, "f": f, "c": c, "backend": jax.default_backend()}
        rows.append({
            "name": f"kernel/v1_int32_b{b}_r{r}_f{f}",
            "us_per_call": us32,
            "derived": (
                f"samples_per_s={b / (us32 * 1e-6):.0f};"
                f"gcompare_per_s={t32['compare_ops'] / (us32 * 1e-6) / 1e9:.2f};"
                f"bytes={t32['bytes_total']:.0f}"
            ),
            "config": {**cfg, "table_dtype": "int32", "mode": "direct"},
        })
        rows.append({
            "name": f"kernel/v2_uint8_b{b}_r{r}_f{f}",
            "us_per_call": us8,
            "derived": (
                f"samples_per_s={b / (us8 * 1e-6):.0f};"
                f"speedup_vs_int32={us32 / us8:.2f};"
                f"bytes={t8['bytes_total']:.0f};"
                f"packed_ratio={t8['packed_ratio']:.1f}"
            ),
            "config": {**cfg, "table_dtype": "uint8", "mode": "inclusive"},
        })
        # the kernel-v3 dispatch outcome on these measurements: the
        # per-bucket winner a TunePlan.dispatch entry would record
        chosen_dtype = "int32" if us32 <= us8 else "uint8"
        us_d = min(us32, us8)
        rows.append({
            "name": f"kernel/v3_dispatch_b{b}_r{r}_f{f}",
            "us_per_call": us_d,
            "derived": (
                f"chosen={kernel_version(chosen_dtype)}_{chosen_dtype};"
                f"v1_us={us32:.0f};v2_us={us8:.0f};"
                f"win_vs_v1={us32 / us_d:.2f}"
            ),
            "config": {
                **cfg, "table_dtype": chosen_dtype,
                "mode": "direct" if chosen_dtype == "int32" else "inclusive",
                "kernel": kernel_version(chosen_dtype),
            },
        })

    # small tiled-Pallas spot row: wildcard-mask + scratch accumulation
    # actually executing (interpret off-TPU => trend only, never gated tight)
    b, r, f, c = 128, 512, 256, 8
    q, low, high, leaf, q8, lo8, hi8 = _problem(rng, b, r, f, c)
    lo_p, hi_p, lm, _ = kops.pack_tables(
        low, high, leaf, r_blk=256, n_bins=256, dtype="uint8"
    )
    mask = kops.wildcard_tile_mask(
        lo_p, hi_p, r_blk=256, f_blk=32, n_bins=256, inclusive=True,
        n_feat=f,
    )
    qp = kops.pad_queries(jnp.asarray(q8), lo_p.shape[0], b_blk=128, dtype="uint8")
    args = (qp, jnp.asarray(lo_p), jnp.asarray(hi_p), jnp.asarray(lm),
            jnp.asarray(mask))
    us = time_call(
        lambda: kops.cam_match(
            *args, out_b=b, out_c=c, b_blk=128, r_blk=256, f_blk=32, n_feat=f,
            mode="inclusive",
        ).block_until_ready()
    )
    rows.append({
        "name": f"kernel/v2_pallas_uint8_b{b}_r{r}_f{f}",
        "us_per_call": us,
        "derived": (
            f"samples_per_s={b / (us * 1e-6):.0f};"
            f"skip_tiles={1.0 - float(np.asarray(mask).mean()):.2f};"
            f"interpret={jax.default_backend() != 'tpu'}"
        ),
        "config": {"b": b, "r": r, "f": f, "c": c, "table_dtype": "uint8",
                   "backend": "pallas", "mode": "inclusive"},
    })
    return rows
