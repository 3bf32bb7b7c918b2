"""jit'd public wrappers around the cam_match Pallas kernel.

Handles the padding contract so callers can pass ragged real-world shapes:
  * batch  -> multiple of b_blk          (pad queries with zeros)
  * rows   -> multiple of r_blk          (pad with never-match ranges)
  * feats  -> the dtype's sublane tile   (pad with always-match ranges)
  * chans  -> multiple of 8              (pad leaf channels with zeros)
and strips the padding from the output.

The padded bound tables are FEATURE-MAJOR, ``(F_pad, R_pad)``: CAM rows on
the lanes, as the kernel compares them (DESIGN.md §10); the leaf matrix
stays ``(R_pad, C_pad)``.  ``pack_tables`` converts the exclusive-high
int32 layout into the compact inclusive-high form in a narrow unsigned
dtype, and ``wildcard_tile_mask`` precomputes the per-(row-tile,
feature-group) activity map the kernel uses to skip all-wildcard groups.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.cam_match import (
    F_CHUNK, cam_match_pallas, n_groups, sublane_rows,
)


def _ceil_to(x: int, m: int) -> int:
    return int(np.ceil(x / m)) * m


def pad_tables(
    low: np.ndarray,
    high: np.ndarray,
    leaf_matrix: np.ndarray,
    *,
    r_blk: int = 256,
    c_mult: int = 8,
    n_bins: int | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad the compiled CAM table to kernel-friendly shapes (host-side).

    Output stays in the canonical exclusive-high int32 encoding, bounds
    feature-major ``(F_pad, R_pad)``; use :func:`pack_tables` for the
    compact-dtype kernel form.
    """
    lo, hi, lm, _ = pack_tables(
        low, high, leaf_matrix, r_blk=r_blk, c_mult=c_mult, n_bins=n_bins,
        dtype="int32", inclusive=False,
    )
    return lo, hi, lm


def pack_tables(
    low: np.ndarray,
    high: np.ndarray,
    leaf_matrix: np.ndarray,
    *,
    r_blk: int = 256,
    c_mult: int = 8,
    n_bins: int | None = None,
    dtype: str = "int32",
    inclusive: bool | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, bool]:
    """Pad + pack the CAM table for the kernel; returns (lo, hi, leaf, incl).

    ``lo``/``hi`` are feature-major ``(F_pad, R_pad)``, features padded to
    the dtype's sublane tile (:func:`sublane_rows`); ``leaf`` is
    ``(R_pad, C_pad)``.  The transpose from the table's ``(R, F)`` happens
    here, once, at bind.

    ``dtype`` is the kernel table dtype.  The packed (unsigned) dtypes
    always store INCLUSIVE upper bounds so the full grid [0, n_bins)
    fits (n_bins=256 would overflow uint8 as an exclusive bound);
    ``inclusive=True`` forces the inclusive encoding for int32 too (the
    engine's mode='inclusive').  Encoding map:

      real cells        low,  high-1       (int32 keeps high-1 exactly,
                                            so degenerate high=0 cells
                                            stay unmatchable at -1)
      always-match pad  0,    n_bins-1
      never-match rows  1,    0            (low > high, unmatchable)

    An unsigned dtype additionally requires every table value to fit its
    range — compile-generated tables always do (high >= low+1 >= 1);
    perturbed ones (defect injection) must use the int32 layout.

    ``dtype='float32'`` is the SOFT cell layout instead: half-integer
    bounds with wildcard cells at (-inf, +inf) and never-match cells at
    (+inf, -inf) (``precision.encode_soft_bounds``), padded with the
    same always-match columns / never-match rows semantics.  Returned
    with ``inclusive=False`` (the soft compare is open-interval on the
    shifted bounds, the exclusive-high family).
    """
    dt = np.dtype(dtype)
    if dt.kind == "f":
        return _pack_tables_soft(
            low, high, leaf_matrix, r_blk=r_blk, c_mult=c_mult, n_bins=n_bins,
        )
    if inclusive is None:
        inclusive = dt.kind == "u"
    if dt.kind == "u" and not inclusive:
        raise ValueError("packed unsigned tables require the inclusive encoding")

    hi_enc = (high.astype(np.int64) - 1) if inclusive else high.astype(np.int64)
    lo_enc = low.astype(np.int64)
    if dt.kind == "u":
        lo_b = int(lo_enc.min(initial=0)), int(lo_enc.max(initial=0))
        hi_b = int(hi_enc.min(initial=0)), int(hi_enc.max(initial=0))
        top = np.iinfo(dt).max
        if lo_b[0] < 0 or hi_b[0] < 0 or lo_b[1] > top or hi_b[1] > top:
            raise ValueError(
                f"table values (low in {lo_b}, inclusive high in {hi_b}) "
                f"do not fit table dtype {dtype!r}; use 'int32' for "
                "perturbed/out-of-grid tables"
            )

    R, F = low.shape
    C = leaf_matrix.shape[1]
    out_dt = dt if dt.kind == "u" else np.dtype(np.int32)
    R_pad, C_pad = _ceil_to(R, r_blk), _ceil_to(C, c_mult)
    F_pad = _ceil_to(max(F, 1), sublane_rows(out_dt))
    big = n_bins if n_bins is not None else (int(high.max(initial=0)) + 1)

    lo = np.zeros((F_pad, R_pad), dtype=out_dt)
    hi = np.full(  # always-match features in the chosen encoding
        (F_pad, R_pad), big - 1 if inclusive else big, dtype=out_dt
    )
    lo[:F, :R] = lo_enc.T
    hi[:F, :R] = hi_enc.T
    lo[:, R:] = 1  # never-match rows: low=1 > high=0 in both encodings
    hi[:, R:] = 0

    lm = np.zeros((R_pad, C_pad), dtype=np.float32)
    lm[:R, :C] = leaf_matrix
    return lo, hi, lm, inclusive


def _pack_tables_soft(
    low: np.ndarray,
    high: np.ndarray,
    leaf_matrix: np.ndarray,
    *,
    r_blk: int,
    c_mult: int,
    n_bins: int | None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, bool]:
    """The float32 soft-mode layout: pad in the canonical int32 form,
    then apply ``precision.encode_soft_bounds`` so padding features become
    exact wildcards (log-score 0) and padding rows exact never-matches
    (score 0) — no soft weight ever leaks out of the real table."""
    from repro.core.precision import encode_soft_bounds

    bins = int(n_bins) if n_bins is not None else (int(high.max(initial=0)) + 1)
    lo, hi, lm = pad_tables(
        low, high, leaf_matrix, r_blk=r_blk, c_mult=c_mult, n_bins=bins,
    )
    lo_f, hi_f = encode_soft_bounds(lo, hi, bins)
    return lo_f, hi_f, lm, False


def wildcard_tile_mask(
    low: np.ndarray,
    high: np.ndarray,
    *,
    r_blk: int,
    f_blk: int,
    n_bins: int,
    inclusive: bool,
    n_feat: int | None = None,
) -> np.ndarray:
    """(R/r_blk, n_groups) int32 — 0 marks an all-wildcard compare group.

    Operates on PADDED (and possibly packed) feature-major tables
    ``(F_pad, R_pad)``; a group is ``f_blk`` of the first ``n_feat``
    features (default all ``F_pad``), the kernel's unit of skipping.  A
    wildcard cell is the full range [0, n_bins) in whichever encoding
    ``inclusive`` names; on float32 soft-encoded tables it is the exact
    (-inf, +inf) cell (log-score 0, so a skipped group contributes nothing
    to the kernel's running log-sum — skipping stays semantics-free).
    Never-match padding rows are not wildcards, so their groups stay
    active and keep their rows unmatchable.
    """
    F_pad, R = low.shape
    n_feat = F_pad if n_feat is None else n_feat
    if R % r_blk:
        raise ValueError(f"padded rows {R} must tile by r_blk={r_blk}")
    lo, hi = low[:n_feat], high[:n_feat]
    if np.dtype(low.dtype).kind == "f":
        act = ~(np.isneginf(lo) & np.isposinf(hi))
    else:
        top = n_bins - 1 if inclusive else n_bins
        act = ~((lo.astype(np.int64) == 0) & (hi.astype(np.int64) >= top))
    n_g = n_groups(n_feat, f_blk)
    grouped = np.zeros((n_g * f_blk, R), dtype=bool)
    grouped[:n_feat] = act
    tiles = grouped.reshape(n_g, f_blk, R // r_blk, r_blk).any(axis=(1, 3))
    return np.ascontiguousarray(tiles.T).astype(np.int32)


def pad_queries(
    q: np.ndarray | jnp.ndarray,
    f_pad: int,
    b_blk: int = 128,
    dtype: str = "int32",
) -> jnp.ndarray:
    B, _ = q.shape
    return pad_to_bucket(q, _ceil_to(B, b_blk), f_pad, dtype=dtype)


def check_query_range(q: np.ndarray | jnp.ndarray, dtype: str) -> None:
    """Reject bins a narrowing cast would WRAP (eager, host-side).

    The v1 int32 compare was accidentally lenient with out-of-range bins
    (value >= high fails every cell); a packed engine casting 300 to
    uint8 would wrap it to 44 and match rows it must not.  Callers
    binning with the model's own quantizer never trip this.
    """
    dt = np.dtype(dtype)
    if dt.kind != "u" or q.size == 0:
        return
    if np.dtype(q.dtype).kind == "u" and np.dtype(q.dtype).itemsize <= dt.itemsize:
        return  # widening or same-width unsigned: no wrap possible
    mn, mx = int(q.min()), int(q.max())
    if mn < 0 or mx > np.iinfo(dt).max:
        raise ValueError(
            f"query bins in [{mn}, {mx}] do not fit table dtype {dtype!r} "
            f"(max {np.iinfo(dt).max}); were these binned with the model's "
            "quantizer?"
        )


def pad_to_bucket(
    q: np.ndarray | jnp.ndarray, bucket_b: int, f_pad: int, dtype: str = "int32"
) -> jnp.ndarray:
    """Pad a coalesced query batch to an explicit serving-bucket shape.

    Batch rows beyond ``B`` are zero vectors — they produce garbage margins
    that the serving un-padder discards; feature columns beyond ``F`` are
    zero, which the always-match column padding of ``pad_tables`` ignores.
    Keeping the target shape explicit (instead of the next ``b_blk``
    multiple) is what lets the serving layer hit one ``jax.jit`` cache
    entry per bucket rather than one per request shape.  ``dtype`` is the
    engine's table dtype — queries compare natively against packed tables.
    """
    B, F = q.shape
    if B > bucket_b:
        raise ValueError(f"batch {B} exceeds bucket {bucket_b}")
    if F > f_pad:
        raise ValueError(f"features {F} exceed padded width {f_pad}")
    check_query_range(q, dtype)
    out = jnp.zeros((bucket_b, f_pad), dtype=np.dtype(dtype))
    return out.at[:B, :F].set(q.astype(np.dtype(dtype)))


@functools.partial(
    jax.jit,
    static_argnames=(
        "b_blk", "r_blk", "f_blk", "mode", "interpret", "out_b", "out_c",
        "tau", "n_feat",
    ),
)
def cam_match(
    q_padded: jnp.ndarray,
    low: jnp.ndarray,
    high: jnp.ndarray,
    leaf: jnp.ndarray,
    tile_mask: jnp.ndarray | None = None,
    bias: jnp.ndarray | None = None,
    *,
    out_b: int,
    out_c: int,
    b_blk: int = 128,
    r_blk: int = 256,
    f_blk: int = F_CHUNK,
    mode: str = "direct",
    interpret: bool | None = None,
    tau: float = 0.0,
    n_feat: int | None = None,
) -> jnp.ndarray:
    """Kernel entry on pre-padded operands; returns unpadded (out_b, out_c).

    ``low``/``high`` are feature-major ``(F_pad, R_pad)``; ``n_feat`` is
    the table's real width (default ``F_pad``), past which nothing is
    compared.
    ``bias`` is the optional (1, C_pad) fused-epilogue row added inside
    the kernel on each output tile's last visit (kernel v3); callers
    fusing it must NOT add the base score again downstream.  ``tau`` is
    the soft-mode temperature (static, like ``mode`` — it selects the
    compiled trace); hard modes ignore it.
    """
    out = cam_match_pallas(
        q_padded, low, high, leaf, tile_mask, bias,
        b_blk=b_blk, r_blk=r_blk, f_blk=f_blk, mode=mode, interpret=interpret,
        tau=tau, n_feat=n_feat,
    )
    return out[:out_b, :out_c]
