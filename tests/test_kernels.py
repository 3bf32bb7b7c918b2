"""Pallas cam_match kernel: shape/dtype/mode sweep vs the ref.py oracle
(interpret=True executes the kernel body on CPU)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.kernels.cam_match import cam_match_pallas
from repro.kernels.ref import cam_match_bits_ref, cam_match_ref


def _random_problem(rng, b, r, f, c, n_bins=256):
    low = rng.integers(0, n_bins, size=(r, f)).astype(np.int32)
    width = rng.integers(0, n_bins, size=(r, f))
    high = np.minimum(low + width, n_bins).astype(np.int32)
    # sprinkle don't-cares
    dc = rng.random((r, f)) < 0.3
    low[dc], high[dc] = 0, n_bins
    leaf = rng.normal(size=(r, c)).astype(np.float32)
    q = rng.integers(0, n_bins, size=(b, f)).astype(np.int32)
    return q, low, high, leaf


@pytest.mark.parametrize("b,r,f,c", [
    (8, 64, 10, 1),
    (64, 512, 130, 8),
    (128, 256, 26, 3),
    (1, 300, 54, 7),
])
@pytest.mark.parametrize("mode", ["direct", "msb_lsb"])
def test_kernel_vs_oracle_shapes(b, r, f, c, mode):
    rng = np.random.default_rng(b * 1000 + r + f + c)
    q, low, high, leaf = _random_problem(rng, b, r, f, c)
    lo_p, hi_p, leaf_p = kops.pad_tables(low, high, leaf, r_blk=256, n_bins=256)
    q_p = kops.pad_queries(jnp.asarray(q), lo_p.shape[0])
    out = kops.cam_match(
        q_p, jnp.asarray(lo_p), jnp.asarray(hi_p), jnp.asarray(leaf_p),
        out_b=b, out_c=c, mode=mode, interpret=True,
    )
    ref = cam_match_ref(jnp.asarray(q), jnp.asarray(low), jnp.asarray(high),
                        jnp.asarray(leaf), mode="direct")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("qdtype", [np.int32, np.uint8])
def test_kernel_query_dtypes(qdtype):
    rng = np.random.default_rng(5)
    q, low, high, leaf = _random_problem(rng, 16, 128, 20, 2)
    lo_p, hi_p, leaf_p = kops.pad_tables(low, high, leaf, n_bins=256)
    q_p = kops.pad_queries(jnp.asarray(q.astype(qdtype)), lo_p.shape[0])
    out = kops.cam_match(q_p, jnp.asarray(lo_p), jnp.asarray(hi_p),
                         jnp.asarray(leaf_p), out_b=16, out_c=2, interpret=True)
    ref = cam_match_ref(jnp.asarray(q), jnp.asarray(low), jnp.asarray(high),
                        jnp.asarray(leaf))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_kernel_16bit_bins_direct_mode():
    """n_bins = 4096 ('unconstrained' grid) — direct mode handles wider
    integer thresholds."""
    rng = np.random.default_rng(6)
    q, low, high, leaf = _random_problem(rng, 8, 128, 12, 1, n_bins=4096)
    lo_p, hi_p, leaf_p = kops.pad_tables(low, high, leaf, n_bins=4096)
    q_p = kops.pad_queries(jnp.asarray(q), lo_p.shape[0])
    out = kops.cam_match(q_p, jnp.asarray(lo_p), jnp.asarray(hi_p),
                         jnp.asarray(leaf_p), out_b=8, out_c=1, interpret=True)
    ref = cam_match_ref(jnp.asarray(q), jnp.asarray(low), jnp.asarray(high),
                        jnp.asarray(leaf))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_block_shape_invariance():
    rng = np.random.default_rng(7)
    q, low, high, leaf = _random_problem(rng, 32, 512, 30, 4)
    outs = []
    for r_blk in (128, 256, 512):
        lo_p, hi_p, leaf_p = kops.pad_tables(low, high, leaf, r_blk=r_blk, n_bins=256)
        q_p = kops.pad_queries(jnp.asarray(q), lo_p.shape[0])
        outs.append(np.asarray(kops.cam_match(
            q_p, jnp.asarray(lo_p), jnp.asarray(hi_p), jnp.asarray(leaf_p),
            out_b=32, out_c=4, r_blk=r_blk, interpret=True,
        )))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-6)
    np.testing.assert_allclose(outs[0], outs[2], rtol=1e-6)


def test_match_bits_oracle_modes_agree():
    rng = np.random.default_rng(8)
    q, low, high, _ = _random_problem(rng, 16, 64, 9, 1)
    args = (jnp.asarray(q), jnp.asarray(low), jnp.asarray(high))
    d = cam_match_bits_ref(*args, mode="direct")
    m = cam_match_bits_ref(*args, mode="msb_lsb")
    c = cam_match_bits_ref(*args, mode="two_cycle")
    assert bool(jnp.all(d == m)) and bool(jnp.all(d == c))


# -- the feature-major kernel against the reference ----------------------------

_FM_MODES = ("direct", "inclusive", "msb_lsb", "two_cycle", "soft")
_FM_DTYPE = {"inclusive": "uint8", "soft": "float32"}  # the rest run int32
_FM_RBLK, _FM_FBLK, _FM_BBLK = 32, 8, 8


@functools.lru_cache(maxsize=None)
def _feature_major_problem(f, skip, padded):
    """Exclusive-high tables (R, F), queries that fall inside chosen rows
    (so margins are not all zero at any width), and a leaf matrix whose
    first two channels are dyadic (every sum exact in float32, whatever
    the order) and last two are normal draws."""
    rng = np.random.default_rng(1000 * f + 10 * skip + padded)
    r = 50 if padded else 64  # two row tiles; 50 leaves never-match padding
    low = rng.integers(0, 200, size=(r, f)).astype(np.int32)
    high = np.minimum(low + rng.integers(1, 160, size=(r, f)), 256)
    high = high.astype(np.int32)
    dc = rng.random((r, f)) < 0.3
    low[dc], high[dc] = 0, 256
    if skip:  # whole feature groups of row tile 0 turn wildcard
        n_g = -(-f // _FM_FBLK)
        for g in {0, n_g - 1}:
            cols = slice(g * _FM_FBLK, min((g + 1) * _FM_FBLK, f))
            low[:_FM_RBLK, cols], high[:_FM_RBLK, cols] = 0, 256
    q = rng.integers(0, 256, size=(16, f)).astype(np.int32)
    for b, row in enumerate(rng.integers(0, r, size=12)):  # inside a row
        q[b] = rng.integers(low[row], high[row])
    q[12], q[13] = 0, 255  # the grid's edges
    leaf = np.concatenate([
        rng.integers(-8, 9, size=(r, 2)) / 16.0,
        rng.normal(size=(r, 2)),
    ], axis=1).astype(np.float32)
    return q, low, high, leaf


def _feature_major_run(q, low, high, leaf, mode):
    """The kernel on the engine's padded, feature-major operands."""
    dtype = _FM_DTYPE.get(mode, "int32")
    lo_p, hi_p, lm, incl = kops.pack_tables(
        low, high, leaf, r_blk=_FM_RBLK, n_bins=256, dtype=dtype,
    )
    f = low.shape[1]
    mask = kops.wildcard_tile_mask(
        lo_p, hi_p, r_blk=_FM_RBLK, f_blk=_FM_FBLK, n_bins=256,
        inclusive=incl, n_feat=f,
    )
    qp = kops.pad_queries(jnp.asarray(q), lo_p.shape[0], b_blk=_FM_BBLK,
                          dtype=dtype)
    out = kops.cam_match(
        qp, jnp.asarray(lo_p), jnp.asarray(hi_p), jnp.asarray(lm),
        jnp.asarray(mask), out_b=q.shape[0], out_c=leaf.shape[1],
        b_blk=_FM_BBLK, r_blk=_FM_RBLK, f_blk=_FM_FBLK, mode=mode,
        interpret=True, tau=0.0, n_feat=f,
    )
    return np.asarray(out), mask, lm


@functools.lru_cache(maxsize=None)
def _feature_major_int32(f, skip, padded):
    """The int32 'direct' kernel's margins on the same problem."""
    return _feature_major_run(*_feature_major_problem(f, skip, padded),
                              "direct")[0]


@pytest.mark.parametrize("padded", [False, True], ids=["rows_exact", "rows_padded"])
@pytest.mark.parametrize("skip", [False, True], ids=["mask_full", "mask_skips"])
@pytest.mark.parametrize("f", [1, 10, 129, 130, 257])
@pytest.mark.parametrize("mode", _FM_MODES)
def test_feature_major_kernel_bit_equal_to_reference(mode, f, skip, padded):
    """Every cell mode at widths around the sublane and group boundaries:
    exact margins equal the reference bit for bit (dyadic leaves), and
    float margins equal both the int32 'direct' kernel at the same r_blk
    and the row-tile order of the leaf dot (one partial dot per row
    tile, summed in tile order)."""
    q, low, high, leaf = _feature_major_problem(f, skip, padded)
    out, mask, lm = _feature_major_run(q, low, high, leaf, mode)
    assert (mask.min() == 0) == skip  # the skip engages only where asked
    ref = np.asarray(cam_match_ref(
        jnp.asarray(q), jnp.asarray(low), jnp.asarray(high),
        jnp.asarray(leaf), mode="direct",
    ))
    assert np.abs(ref[:, :2]).sum() > 0  # some rows do match
    np.testing.assert_array_equal(out[:, :2], ref[:, :2])

    np.testing.assert_array_equal(out, _feature_major_int32(f, skip, padded))

    match = np.zeros((q.shape[0], lm.shape[0]), np.float32)
    match[:, : low.shape[0]] = np.asarray(cam_match_bits_ref(
        jnp.asarray(q), jnp.asarray(low), jnp.asarray(high), mode="direct",
    ))
    tiled = None
    for r0 in range(0, lm.shape[0], _FM_RBLK):
        part = jax.lax.dot(
            jnp.asarray(match[:, r0 : r0 + _FM_RBLK]),
            jnp.asarray(lm[r0 : r0 + _FM_RBLK]),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        tiled = part if tiled is None else tiled + part
    np.testing.assert_array_equal(out, np.asarray(tiled)[:, : leaf.shape[1]])
