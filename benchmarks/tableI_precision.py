"""Table I / Eq. 3: precision-doubling scheme — equivalence count over the
full 8-bit space and relative cost of the three kernel modes."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_call
from repro.core import precision
from repro.kernels import ops as kops


def run() -> list[dict]:
    rows = []
    rng = np.random.default_rng(0)
    q = jnp.arange(256)[:, None]
    tl = jnp.asarray(rng.integers(0, 256, size=65536))[None, :]
    th = jnp.asarray(rng.integers(0, 257, size=65536))[None, :]
    d = precision.match_direct(q, tl, th)
    m = precision.match_msb_lsb(q, tl, th)
    c = precision.match_two_cycle(q, tl, th)
    rows.append({
        "name": "tableI/equivalence",
        "us_per_call": 0.0,
        "derived": f"cases={256*65536};msb_lsb_equal={bool(jnp.all(d==m))};"
                   f"two_cycle_equal={bool(jnp.all(d==c))}",
    })

    # kernel-mode relative cost (interpret mode, CPU)
    b, r, f, cch = 128, 1024, 130, 8
    low = rng.integers(0, 256, size=(r, f)).astype(np.int32)
    high = np.minimum(low + rng.integers(0, 256, size=(r, f)), 256).astype(np.int32)
    leaf = rng.normal(size=(r, cch)).astype(np.float32)
    lo_p, hi_p, leaf_p = kops.pad_tables(low, high, leaf, n_bins=256)
    q_p = kops.pad_queries(jnp.asarray(rng.integers(0, 256, (b, f))), lo_p.shape[0])
    for mode in ("direct", "msb_lsb", "two_cycle"):
        us = time_call(
            lambda: kops.cam_match(
                q_p, jnp.asarray(lo_p), jnp.asarray(hi_p), jnp.asarray(leaf_p),
                out_b=b, out_c=cch, mode=mode, interpret=True,
            ).block_until_ready()
        )
        rows.append({
            "name": f"tableI/kernel_{mode}",
            "us_per_call": us,
            "derived": f"B={b};R={r};F={f}",
        })
    return rows
