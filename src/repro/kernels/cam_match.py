"""Pallas TPU kernel for the X-TIME CAM search + leaf accumulation.

This is the compute hot-spot the paper implements in analog hardware: a
massively parallel range compare between a query tile and every stored CAM
row, AND-reduced over feature columns (the match line), followed by the
leaf-value accumulation (MMR + SRAM + ACC path).

Layout (DESIGN.md §10): the bound tables are FEATURE-MAJOR, ``(F_pad, R)``,
so CAM rows lie on the 128 lanes and features on sublanes.  The match
line of a (batch tile, row tile) pair is built in the layout the leaf dot
consumes, ``(b_blk, r_blk)`` with rows on lanes, by one element-wise
compare-and-AND per real feature:

    acc[:, lanes] &= cell(qb[f], low[f, lanes], high[f, lanes])

``qb[f]`` is query column f broadcast across 128 lanes, held in a VMEM
scratch ``(n_feat, b_blk, 128)`` that a loop over the columns fills once
per batch tile (at the first row tile); ``low[f, lanes]`` is one table row broadcast across
the batch's sublanes.  No step reduces across lanes: the AND over
features is a chain of element-wise ANDs.

  * **compact dtypes** — the tables stream in the narrowest dtype the
    bin grid permits (uint8 for the paper's native 256 bins, uint16 to
    65536, int32 beyond / for the faithful cell modes).  Packed tables
    store INCLUSIVE upper bounds so [0, n_bins) fits the dtype.  Inside
    the kernel each loaded group of table rows widens to int32 once,
    before its compares; HBM and VMEM stay narrow.
  * **real features only** — features pad on sublanes (to the dtype's
    sublane tile), but the compare loop stops at the table's real width
    ``n_feat``: padding costs bytes, never compares.  The loop runs over
    feature GROUPS of ``f_blk`` features (a ``fori_loop``), unrolled
    within a group, so code size does not grow with the width.
  * **wildcard group skipping** — a per-(row-tile, feature-group)
    activity mask lets the kernel skip a group's compares where all its
    cells are wildcards (an all-wildcard group matches everything).  The
    mask rides scalar prefetch as a FLAT 1-D SMEM vector
    (``j * n_groups + g``): a 2-D SMEM array pads its minor dim.

Grid = (B/b_blk, R/r_blk); the batch axis is parallel, the row axis
``arbitrary`` (sequential) so the query broadcast scratch carries across
row tiles and the output tile accumulates in place.  The leaf matmul
``match(b_blk, r_blk) @ leaf(r_blk, C)`` fires once per row tile, on the
MXU — the systolic replacement for the analog wired-OR / sequential MMR.
Compiled, ``r_blk`` must be a multiple of 128 (rows are on lanes).

The ``mode`` switch selects the cell-level comparison:
  'direct'    — ideal 8/16-bit compare on exclusive-high int32 tables,
  'inclusive' — the packed-table compare (low <= q <= high, inclusive bound),
  'msb_lsb'   — the paper's Eq. 3 macro-cell arithmetic (faithful mode),
  'two_cycle' — Table-I cycle-accurate discharge semantics,
  'soft'      — sigmoid match SCORES on float32 soft-encoded tables
                (DESIGN.md §15): the match line carries a running SUM of
                per-cell log-scores (the additive twin of the running
                AND; a skipped all-wildcard group adds exactly 0), and
                the final exp lands on the MXU dot as the (B_blk, R_blk)
                score matrix.  ``tau`` (static, bin units) sets the
                boundary temperature; tau=0 is the exact hard indicator,
                bit-equal to 'direct' margins at identical tile sizes.
The four hard modes are bit-equivalent on equivalently-encoded tables
(property-tested); 'soft' at tau=0 joins that equivalence class.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import precision

_CELL_MATCH = {
    "direct": precision.match_direct,
    "inclusive": precision.match_inclusive,  # compact tables (§Perf X1)
    "msb_lsb": precision.match_msb_lsb,
    "two_cycle": precision.match_two_cycle,
}

# default feature group: the compares of one group unroll in the kernel
# body, and the wildcard mask has one entry per (row tile, group).  16
# keeps the unrolled body, which every program traces again, small
F_CHUNK = 16
LANES = 128


def sublane_rows(dtype) -> int:
    """Rows of one sublane tile for ``dtype``: 8 for 32-bit, 16 for
    16-bit, 32 for 8-bit — the feature padding of a feature-major table."""
    return 32 // np.dtype(dtype).itemsize


def n_groups(n_feat: int, f_blk: int) -> int:
    """Feature groups the kernel loops over (and mask columns)."""
    return -(-n_feat // f_blk)


def default_interpret() -> bool:
    """Resolve the 'auto' interpret policy: compiled on TPU, interpreter
    everywhere else (running the interpreter on real hardware silently
    costs orders of magnitude — the old ``interpret=True`` default bug)."""
    return jax.default_backend() != "tpu"


def _cam_match_kernel(
    mask_ref,  # (n_r_tiles * n_groups,) int32 SMEM — flat group activity
    q_ref,  # (b_blk, F_pad) table dtype
    low_ref,  # (F_pad, r_blk) table dtype
    high_ref,  # (F_pad, r_blk) table dtype
    leaf_ref,  # (r_blk, C_pad) float32
    *refs,  # [bias_ref,] out_ref, qb_ref (n_feat, b_blk, lane_w), acc_ref
    mode: str,
    n_feat: int,
    f_blk: int,
    n_r_tiles: int,
    fuse_bias: bool,
    tau: float,
):
    if fuse_bias:
        bias_ref, out_ref, qb_ref, acc_ref = refs
    else:
        out_ref, qb_ref, acc_ref = refs
        bias_ref = None
    j = pl.program_id(1)
    soft = mode == "soft"
    cell = None if soft else _CELL_MATCH[mode]
    cdt = jnp.float32 if soft else jnp.int32  # the compare's dtype
    b_blk, lane_w = qb_ref.shape[1:]
    f_pad, r_blk = low_ref.shape
    n_g = n_groups(n_feat, f_blk)
    sub = sublane_rows(low_ref.dtype)

    @pl.when(j == 0)
    def _broadcast_queries():  # once per batch tile: columns across lanes
        q = q_ref[...].astype(cdt)
        col = jax.lax.broadcasted_iota(jnp.int32, q.shape, 1)

        def fill(f, carry):  # column f alone survives the sum: exact
            q_f = jnp.sum(jnp.where(col == f, q, 0), axis=1, keepdims=True)
            qb_ref[f] = jnp.broadcast_to(q_f, (b_blk, lane_w))
            return carry

        jax.lax.fori_loop(0, n_feat, fill, 0)

    # the match line starts charged: all-match (log-score 0 for 'soft')
    acc_ref[...] = jnp.full(acc_ref.shape, 0 if soft else 1, acc_ref.dtype)

    def compare(g, f0, n, n_load):
        """AND features f0 .. f0+n-1 (group g; n_load rows loaded) into
        the match line."""

        @pl.when(mask_ref[j * n_g + g] != 0)
        def _():  # skipped for all-wildcard groups (they match everything)
            for lane0 in range(0, r_blk, lane_w):
                cols = pl.ds(lane0, lane_w)
                lo = low_ref[pl.ds(f0, n_load), cols].astype(cdt)
                hi = high_ref[pl.ds(f0, n_load), cols].astype(cdt)
                acc = acc_ref[:, cols]
                # lax, not jnp: every serving bucket traces this body again
                for u in range(n):
                    qf = qb_ref[f0 + u]
                    lo_u = jax.lax.slice_in_dim(lo, u, u + 1)
                    hi_u = jax.lax.slice_in_dim(hi, u, u + 1)
                    if soft:
                        acc = acc + precision.soft_cell_logscore(
                            qf, lo_u, hi_u, tau
                        )
                    else:
                        ok = jax.lax.convert_element_type(
                            cell(qf, lo_u, hi_u), jnp.int32
                        )
                        acc = jax.lax.bitwise_and(acc, ok)
                acc_ref[:, cols] = acc

    n_full, tail = divmod(n_feat, f_blk)
    if n_full:

        def group(g, carry):
            compare(g, pl.multiple_of(g * f_blk, f_blk), f_blk, f_blk)
            return carry

        jax.lax.fori_loop(0, n_full, group, 0)
    if tail:  # loads whole sublane tiles, compares only the real rows
        f0 = n_full * f_blk
        compare(n_full, f0, tail, min(-(-tail // sub) * sub, f_pad - f0))

    match = jnp.exp(acc_ref[...]) if soft else acc_ref[...].astype(jnp.float32)
    # HIGHEST: the TPU's default f32 matmul rounds the leaf values to
    # bfloat16 (about 1e-3 relative); the margins must stay float32
    partial = jax.lax.dot(
        match,
        leaf_ref[...],
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (b_blk, C_pad)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(j > 0)
    def _acc():
        out_ref[...] += partial

    if fuse_bias:
        # fused epilogue: the base score lands on the LAST visit of this
        # output tile (row axis is sequential, so j runs in order), AFTER
        # the final partial — the same float order as the separate
        # epilogue pass ((p_0 + ... + p_last) + base), hence bit-identical,
        # without its extra HBM round-trip.
        @pl.when(j == n_r_tiles - 1)
        def _bias():
            out_ref[...] += bias_ref[...]


def full_tile_mask(n_r_tiles: int, n_g: int) -> jnp.ndarray:
    """The every-group-active mask — the EXPLICIT form of 'no mask given'.

    ``cam_match_pallas(tile_mask=None)`` builds exactly this, so callers
    without wildcard analysis pay the full compare on every group (never
    a silent skip).  Kept public so tests and callers can assert the
    fallback's shape/semantics instead of shape-inferring it.
    """
    return jnp.ones((n_r_tiles, n_g), dtype=jnp.int32)


def _vmem_limit(b_blk, r_blk, f_pad, c_pad, lane_w, n_feat, itemsize) -> int:
    """Scoped VMEM the kernel needs: double-buffered blocks plus scratch,
    with room for the compiler's own temporaries."""
    lanes = lambda n: -(-n // LANES) * LANES  # noqa: E731 - lane padding
    blocks = (
        b_blk * lanes(f_pad) * itemsize  # queries
        + 2 * f_pad * lanes(r_blk) * itemsize  # low, high
        + r_blk * lanes(c_pad) * 4  # leaves
        + b_blk * lanes(c_pad) * 4  # output
    )
    scratch = n_feat * b_blk * lanes(lane_w) * 4 + b_blk * lanes(r_blk) * 4
    return 2 * blocks + scratch + (8 << 20)


@functools.partial(
    jax.jit,
    static_argnames=(
        "b_blk", "r_blk", "f_blk", "mode", "interpret", "tau", "n_feat",
    ),
)
def cam_match_pallas(
    q: jnp.ndarray,  # (B, F_pad) table dtype — pre-padded (see ops.py)
    low: jnp.ndarray,  # (F_pad, R) table dtype, feature-major
    high: jnp.ndarray,  # (F_pad, R) table dtype, feature-major
    leaf: jnp.ndarray,  # (R, C_pad) float32
    tile_mask: jnp.ndarray | None = None,  # (R/r_blk, n_groups) int32
    bias: jnp.ndarray | None = None,  # (1, C_pad) float32 fused epilogue
    *,
    b_blk: int = 128,
    r_blk: int = 256,
    f_blk: int = F_CHUNK,
    mode: str = "direct",
    interpret: bool | None = None,
    tau: float = 0.0,
    n_feat: int | None = None,
) -> jnp.ndarray:
    """(B, C_pad) accumulated logits.  B and R must divide their blocks.

    ``n_feat`` is the table's real width (default ``F_pad``): features
    at and beyond it are padding and are never compared.
    ``tile_mask[j, g] == 0`` marks an all-wildcard (always-match) group
    of ``f_blk`` features in row tile j, whose compares the kernel skips;
    ``None`` falls back EXPLICITLY to :func:`full_tile_mask` (every group
    compared), and a mask of the wrong shape is rejected here — under
    interpret mode a misshapen mask would otherwise read out-of-bounds
    activity bits and silently skip live groups.  ``bias`` fuses the
    epilogue's base-score add into the last row tile of each output tile
    — bit-identical to adding it after the kernel (same float order), one
    less HBM round-trip.  ``interpret=None`` resolves via
    :func:`default_interpret` (compiled on TPU only).
    """
    B, F_pad = q.shape
    R = low.shape[1]
    C_pad = leaf.shape[1]
    if interpret is None:
        interpret = default_interpret()
    if low.shape[0] != F_pad or high.shape != low.shape:
        raise ValueError(
            f"tables {low.shape}/{high.shape} must be feature-major "
            f"(F_pad={F_pad}, R) like the queries' width"
        )
    if n_feat is None:
        n_feat = F_pad
    if not 0 < n_feat <= F_pad:
        raise ValueError(f"n_feat={n_feat} must lie in [1, F_pad={F_pad}]")
    if B % b_blk or R % r_blk:
        raise ValueError(f"B={B} R={R} must be multiples of ({b_blk}, {r_blk})")
    if not interpret and r_blk % LANES:
        raise ValueError(
            f"r_blk={r_blk} must be a multiple of {LANES} on the TPU: CAM "
            "rows lie on the lanes of the feature-major tables"
        )
    lane_w = LANES if r_blk % LANES == 0 else r_blk
    n_r_tiles, n_g = R // r_blk, n_groups(n_feat, f_blk)
    if tile_mask is None:
        tile_mask = full_tile_mask(n_r_tiles, n_g)
    elif tuple(tile_mask.shape) != (n_r_tiles, n_g):
        raise ValueError(
            f"tile_mask shape {tuple(tile_mask.shape)} does not tile "
            f"(R={R}, n_feat={n_feat}) by (r_blk={r_blk}, f_blk={f_blk}); "
            f"expected ({n_r_tiles}, {n_g}) — pass None for the "
            "explicit every-group-active fallback (full_tile_mask)"
        )
    if tile_mask.dtype != jnp.int32:
        tile_mask = tile_mask.astype(jnp.int32)
    if bias is not None and tuple(bias.shape) != (1, C_pad):
        raise ValueError(
            f"bias shape {tuple(bias.shape)} must be (1, C_pad={C_pad})"
        )

    kernel = functools.partial(
        _cam_match_kernel, mode=mode, n_feat=n_feat, f_blk=f_blk,
        n_r_tiles=n_r_tiles, fuse_bias=bias is not None, tau=float(tau),
    )
    # the compare's dtype: the running AND's bits for the hard modes, the
    # running log-score sum for 'soft'
    cdt = jnp.float32 if mode == "soft" else jnp.int32
    # index maps take the prefetched mask as a trailing argument
    in_specs = [
        pl.BlockSpec((b_blk, F_pad), lambda i, j, m: (i, 0)),  # queries
        pl.BlockSpec((F_pad, r_blk), lambda i, j, m: (0, j)),  # CAM low
        pl.BlockSpec((F_pad, r_blk), lambda i, j, m: (0, j)),  # CAM high
        pl.BlockSpec((r_blk, C_pad), lambda i, j, m: (j, 0)),  # leaf matrix
    ]
    operands = [q, low, high, leaf]
    if bias is not None:  # fused epilogue bias, one (1, C_pad) row
        in_specs.append(pl.BlockSpec((1, C_pad), lambda i, j, m: (0, 0)))
        operands.append(bias)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # the flat group-activity mask, in SMEM
            grid=(B // b_blk, n_r_tiles),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((b_blk, C_pad), lambda i, j, m: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((n_feat, b_blk, lane_w), cdt),  # query columns
                pltpu.VMEM((b_blk, r_blk), cdt),  # the match line
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, C_pad), jnp.float32),
        # batch axis parallel; the row axis sequential (the query scratch
        # is filled at its first step, the output tile accumulates)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                b_blk, r_blk, F_pad, C_pad, lane_w, n_feat,
                np.dtype(low.dtype).itemsize,
            ),
        ),
        interpret=interpret,
    )(tile_mask.reshape(-1), *operands)
