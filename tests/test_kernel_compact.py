"""Compact uint8 / inclusive-bound kernel mode (§Perf X1 table format)."""

import jax.numpy as jnp
import numpy as np
from oracles import compact_problem

from repro.kernels.cam_match import cam_match_pallas
from repro.kernels.ref import cam_match_ref


def test_inclusive_uint8_kernel_matches_oracle():
    rng = np.random.default_rng(11)
    b, r, f, c = 128, 512, 128, 8
    q, low, high, leaf = compact_problem(rng, b, r, f, c)
    out = cam_match_pallas(  # the kernel reads feature-major tables
        jnp.asarray(q), jnp.asarray(low.T), jnp.asarray(high.T),
        jnp.asarray(leaf), b_blk=128, r_blk=256, mode="inclusive",
        interpret=True,
    )
    ref = cam_match_ref(
        jnp.asarray(q), jnp.asarray(low), jnp.asarray(high), jnp.asarray(leaf),
        mode="inclusive",
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_inclusive_equals_exclusive_semantics():
    """inclusive(low, high-1) == direct(low, high) for high >= 1."""
    rng = np.random.default_rng(12)
    b, r, f, c = 32, 128, 16, 2
    low = rng.integers(0, 200, size=(r, f)).astype(np.int32)
    high = low + rng.integers(1, 56, size=(r, f))  # exclusive, >= low+1
    leaf = rng.normal(size=(r, c)).astype(np.float32)
    q = rng.integers(0, 256, size=(b, f)).astype(np.int32)
    a = cam_match_ref(jnp.asarray(q), jnp.asarray(low), jnp.asarray(high),
                      jnp.asarray(leaf), mode="direct")
    b_ = cam_match_ref(jnp.asarray(q), jnp.asarray(low), jnp.asarray(high - 1),
                       jnp.asarray(leaf), mode="inclusive")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-6)
