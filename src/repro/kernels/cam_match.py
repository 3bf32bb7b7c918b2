"""Pallas TPU kernel for the X-TIME CAM search + leaf accumulation (v2).

This is the compute hot-spot the paper implements in analog hardware: a
massively parallel range compare between a query tile and every stored CAM
row, AND-reduced over feature columns (the match line), followed by the
leaf-value accumulation (MMR + SRAM + ACC path).

Kernel v2 (DESIGN.md §10) differs from the v1 layout in three ways:

  * **compact dtypes** — the threshold tables stream in the narrowest
    dtype the bin grid permits (uint8 for the paper's native 256 bins,
    uint16 to 65536, int32 beyond / for the faithful cell modes).  Packed
    tables store INCLUSIVE upper bounds so [0, n_bins) fits the dtype —
    4x less HBM traffic than the v1 int32 tables at identical results.
    Inside the kernel the loaded tiles widen to int32 before the compare
    (Mosaic cannot lay out the packed 3-D broadcast compare); HBM and
    VMEM stay narrow;
  * **feature grid dimension** — the in-kernel Python loop over feature
    chunks is replaced by a third (feature) grid axis.  The running AND
    accumulates in a (b_blk, r_blk) VMEM scratch across feature tiles,
    so the working set is (r_blk, f_blk) instead of (r_blk, F_pad);
  * **wildcard tile skipping** — a per-(row-tile, feature-tile) activity
    mask lets the kernel skip the compare for tiles that are all
    wildcards (an all-wildcard tile matches everything).  The compiler's
    wildcard-aware row ordering maximizes such tiles.  The mask rides
    scalar prefetch as a FLAT 1-D SMEM vector (``j * n_f_tiles + k``):
    a (1, 1) VMEM block breaks the TPU's (8, 128) tiling rule, and a 2-D
    SMEM array pads its minor dim — 2 MiB at paper scale, over SMEM.

Grid = (B/b_blk, R/r_blk, F_pad/f_blk); the batch axis is parallel, the
row and feature axes are ``arbitrary`` (sequential) so the scratch AND
and the output row-accumulation run in place.  The leaf matmul
``match(B_blk, R_blk) @ leaf(R_blk, C)`` fires once per row tile, on the
MXU — the systolic replacement for the analog wired-OR / sequential MMR.

The ``mode`` switch selects the cell-level comparison:
  'direct'    — ideal 8/16-bit compare on exclusive-high int32 tables,
  'inclusive' — the packed-table compare (low <= q <= high, inclusive bound),
  'msb_lsb'   — the paper's Eq. 3 macro-cell arithmetic (faithful mode),
  'two_cycle' — Table-I cycle-accurate discharge semantics,
  'soft'      — sigmoid match SCORES on float32 soft-encoded tables
                (DESIGN.md §15): the scratch carries a running SUM of
                per-cell log-scores (the additive twin of the running
                AND; a skipped all-wildcard tile adds exactly 0), and
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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import precision

_CELL_MATCH = {
    "direct": precision.match_direct,
    "inclusive": precision.match_inclusive,  # compact tables (§Perf X1)
    "msb_lsb": precision.match_msb_lsb,
    "two_cycle": precision.match_two_cycle,
}

# default feature-axis tile; 128 lanes wide, small enough that the
# (b_blk, r_blk, f_blk) compare temp stays well under VMEM budget.
F_CHUNK = 128


def default_interpret() -> bool:
    """Resolve the 'auto' interpret policy: compiled on TPU, interpreter
    everywhere else (running the interpreter on real hardware silently
    costs orders of magnitude — the old ``interpret=True`` default bug)."""
    return jax.default_backend() != "tpu"


def _cam_match_kernel(
    mask_ref,  # (n_r_tiles * n_f_tiles,) int32 SMEM — flat tile activity
    q_ref,  # (B_blk, f_blk) table dtype
    low_ref,  # (R_blk, f_blk) table dtype
    high_ref,  # (R_blk, f_blk) table dtype
    leaf_ref,  # (R_blk, C_pad) float32
    *refs,  # [bias_ref (1, C_pad) float32 when fused,] out_ref, acc_ref
    mode: str,
    n_f_tiles: int,
    n_r_tiles: int,
    fuse_bias: bool,
    tau: float,
):
    if fuse_bias:
        bias_ref, out_ref, acc_ref = refs
    else:
        out_ref, acc_ref = refs
        bias_ref = None
    j = pl.program_id(1)
    k = pl.program_id(2)
    soft = mode == "soft"
    cell = None if soft else _CELL_MATCH[mode]

    @pl.when(k == 0)
    def _precharge():  # the match line starts charged (all-match)
        if soft:  # log-score 0 == score 1 (the charged analog line)
            acc_ref[...] = jnp.zeros_like(acc_ref[...])
        else:
            acc_ref[...] = jnp.ones_like(acc_ref[...])

    @pl.when(mask_ref[j * n_f_tiles + k] != 0)
    def _compare():  # skipped for all-wildcard tiles (they match everything)
        q, lo, hi = q_ref[...], low_ref[...], high_ref[...]
        if not soft and q.dtype != jnp.int32:
            # packed uint8/uint16 tiles widen after the load: exact, and
            # the int32 broadcast compare is one Mosaic can lay out
            q, lo, hi = (x.astype(jnp.int32) for x in (q, lo, hi))
        q = q[:, None, :]  # (B_blk, 1, f_blk)
        lo = lo[None, :, :]  # (1, R_blk, f_blk)
        hi = hi[None, :, :]
        if soft:
            logs = precision.soft_cell_logscore(q, lo, hi, tau)
            acc_ref[...] += jnp.sum(logs, axis=-1)  # (B_blk, R_blk)
        else:
            ok = jnp.all(cell(q, lo, hi), axis=-1)  # (B_blk, R_blk)
            acc_ref[...] = acc_ref[...] & ok.astype(jnp.int32)

    @pl.when(k == n_f_tiles - 1)
    def _accumulate():  # MXU leaf gather once the match line is final
        match = (
            jnp.exp(acc_ref[...]) if soft
            else acc_ref[...].astype(jnp.float32)
        )
        # HIGHEST: the TPU's default f32 matmul rounds the leaf values to
        # bfloat16 (about 1e-3 relative); the margins must stay float32
        partial = jax.lax.dot(
            match,
            leaf_ref[...],
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )  # (B_blk, C_pad)

        @pl.when(j == 0)
        def _init():
            out_ref[...] = partial

        @pl.when(j > 0)
        def _acc():
            out_ref[...] += partial

        if fuse_bias:
            # fused epilogue: the base score lands on the LAST visit of
            # this output tile (row axis is sequential, so j runs in
            # order), AFTER the final partial — the same float order as
            # the separate epilogue pass ((p_0 + ... + p_last) + base),
            # hence bit-identical, without its extra HBM round-trip.
            @pl.when(j == n_r_tiles - 1)
            def _bias():
                out_ref[...] += bias_ref[...]


def full_tile_mask(n_r_tiles: int, n_f_tiles: int) -> jnp.ndarray:
    """The every-tile-active mask — the EXPLICIT form of 'no mask given'.

    ``cam_match_pallas(tile_mask=None)`` builds exactly this, so callers
    without wildcard analysis pay the full compare on every tile (never a
    silent skip).  Kept public so tests and callers can assert the
    fallback's shape/semantics instead of shape-inferring it.
    """
    return jnp.ones((n_r_tiles, n_f_tiles), dtype=jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("b_blk", "r_blk", "f_blk", "mode", "interpret", "tau"),
)
def cam_match_pallas(
    q: jnp.ndarray,  # (B, F_pad) table dtype — pre-padded (see ops.py)
    low: jnp.ndarray,  # (R, F_pad) table dtype
    high: jnp.ndarray,  # (R, F_pad) table dtype
    leaf: jnp.ndarray,  # (R, C_pad) float32
    tile_mask: jnp.ndarray | None = None,  # (R/r_blk, F_pad/f_blk) int32
    bias: jnp.ndarray | None = None,  # (1, C_pad) float32 fused epilogue
    *,
    b_blk: int = 128,
    r_blk: int = 256,
    f_blk: int = F_CHUNK,
    mode: str = "direct",
    interpret: bool | None = None,
    tau: float = 0.0,
) -> jnp.ndarray:
    """(B, C_pad) accumulated logits.  All dims must divide their blocks.

    ``tile_mask[j, k] == 0`` marks an all-wildcard (always-match) tile the
    compare may skip; ``None`` falls back EXPLICITLY to
    :func:`full_tile_mask` (every tile compared), and a mask of the wrong
    shape is rejected here — under interpret mode a misshapen mask would
    otherwise read out-of-bounds activity bits and silently skip live
    tiles.  ``bias`` fuses the epilogue's base-score add into the last
    (row, feature) visit of each output tile — bit-identical to adding it
    after the kernel (same float order), one less HBM round-trip.
    ``interpret=None`` resolves via :func:`default_interpret` (compiled on
    TPU only).
    """
    B, F_pad = q.shape
    R = low.shape[0]
    C_pad = leaf.shape[1]
    if interpret is None:
        interpret = default_interpret()
    if B % b_blk or R % r_blk:
        raise ValueError(f"B={B} R={R} must be multiples of ({b_blk}, {r_blk})")
    if F_pad % f_blk:
        raise ValueError(f"F_pad={F_pad} must be a multiple of f_blk={f_blk}")
    n_f_tiles = F_pad // f_blk
    n_r_tiles = R // r_blk
    if tile_mask is None:
        tile_mask = full_tile_mask(n_r_tiles, n_f_tiles)
    elif tuple(tile_mask.shape) != (n_r_tiles, n_f_tiles):
        raise ValueError(
            f"tile_mask shape {tuple(tile_mask.shape)} does not tile "
            f"(R={R}, F_pad={F_pad}) by (r_blk={r_blk}, f_blk={f_blk}); "
            f"expected ({n_r_tiles}, {n_f_tiles}) — pass None for the "
            "explicit every-tile-active fallback (full_tile_mask)"
        )
    if tile_mask.dtype != jnp.int32:
        tile_mask = tile_mask.astype(jnp.int32)
    if bias is not None and tuple(bias.shape) != (1, C_pad):
        raise ValueError(
            f"bias shape {tuple(bias.shape)} must be (1, C_pad={C_pad})"
        )

    grid = (B // b_blk, R // r_blk, n_f_tiles)
    kernel = functools.partial(
        _cam_match_kernel, mode=mode, n_f_tiles=n_f_tiles,
        n_r_tiles=n_r_tiles, fuse_bias=bias is not None, tau=float(tau),
    )

    # the running accumulator: wired-AND bits for the hard modes, the
    # running log-score sum for 'soft'
    acc_dtype = jnp.float32 if mode == "soft" else jnp.int32
    # index maps take the prefetched mask as a trailing argument
    in_specs = [
        pl.BlockSpec((b_blk, f_blk), lambda i, j, k, m: (i, k)),  # queries
        pl.BlockSpec((r_blk, f_blk), lambda i, j, k, m: (j, k)),  # CAM low
        pl.BlockSpec((r_blk, f_blk), lambda i, j, k, m: (j, k)),  # CAM high
        pl.BlockSpec((r_blk, C_pad), lambda i, j, k, m: (j, 0)),  # leaf matrix
    ]
    operands = [q, low, high, leaf]
    if bias is not None:  # fused epilogue bias, one (1, C_pad) row
        in_specs.append(pl.BlockSpec((1, C_pad), lambda i, j, k, m: (0, 0)))
        operands.append(bias)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # the flat tile-activity mask, in SMEM
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((b_blk, C_pad), lambda i, j, k, m: (i, 0)),
            scratch_shapes=[pltpu.VMEM((b_blk, r_blk), acc_dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, C_pad), jnp.float32),
        # batch axis parallel; row + feature axes sequential (the scratch
        # AND and the output tile accumulate in place)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(tile_mask.reshape(-1), *operands)
