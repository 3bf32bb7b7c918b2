"""Pure-jnp oracle for the cam_match kernel.

Semantics (the whole X-TIME datapath between DAC and router, §III-A):

    match[b, r] = AND_f ( low[r, f] <= q[b, f] < high[r, f] )
    out[b, c]   = SUM_r match[b, r] * leaf_matrix[r, c]

Exactly one row per tree matches any query (the leaves of a tree partition
feature space), so the masked sum over a tree's rows equals that tree's
leaf lookup; summing over all rows is the in-core ACC + NoC reduction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import precision


def cam_match_ref(
    q: jnp.ndarray,  # (B, F) integer bins (float32 for mode='soft')
    low: jnp.ndarray,  # (R, F) inclusive lower bin bounds
    high: jnp.ndarray,  # (R, F) exclusive upper bin bounds
    leaf_matrix: jnp.ndarray,  # (R, C) leaf values routed to class channels
    *,
    mode: str = "direct",  # any repro.core.precision.CELL_MODES name
    tau: float = 0.0,  # soft-mode boundary temperature (ignored otherwise)
) -> jnp.ndarray:
    """Returns (B, C) accumulated logits/votes.

    ``mode='soft'`` expects the float32 soft-encoded bounds
    (``precision.encode_soft_bounds``) and aggregates sigmoid match
    SCORES instead of a boolean match line — the (B, R) score matrix
    multiplies the leaf matrix exactly like the hard 0/1 match, so at
    ``tau=0`` the two paths are the same dot product over the same
    operand shapes (bit-equal margins).
    """
    if mode == "soft":
        match = precision.soft_match_scores(q, low, high, tau)  # (B, R)
        return _leaf_dot(match, leaf_matrix)  # (B, C)
    qe = q[:, None, :].astype(jnp.int32)  # (B, 1, F)
    lo = low[None, :, :].astype(jnp.int32)  # (1, R, F)
    hi = high[None, :, :].astype(jnp.int32)
    if mode == "direct":
        cell = precision.match_direct(qe, lo, hi)
    elif mode == "inclusive":
        cell = precision.match_inclusive(
            q[:, None, :], low[None, :, :], high[None, :, :]
        )
    elif mode == "msb_lsb":
        cell = precision.match_msb_lsb(qe, lo, hi)
    elif mode == "two_cycle":
        cell = precision.match_two_cycle(qe, lo, hi)
    else:
        raise ValueError(
            f"unknown mode {mode!r}; registered modes: {precision.mode_names()}"
        )
    match = jnp.all(cell, axis=-1)  # (B, R) — the MAL wired-AND over columns
    return _leaf_dot(match.astype(leaf_matrix.dtype), leaf_matrix)  # (B, C)


def _leaf_dot(match: jnp.ndarray, leaf_matrix: jnp.ndarray) -> jnp.ndarray:
    """``match @ leaf_matrix`` in full float32: a TPU's default matmul
    precision would round the leaf values to bfloat16."""
    return jnp.matmul(match, leaf_matrix, precision=jax.lax.Precision.HIGHEST)


def cam_match_bits_ref(
    q: jnp.ndarray, low: jnp.ndarray, high: jnp.ndarray, *, mode: str = "direct"
) -> jnp.ndarray:
    """(B, R) boolean match lines only (for MMR / debug paths)."""
    if mode == "inclusive":  # packed tables compare in their native dtype
        return jnp.all(
            precision.match_inclusive(
                q[:, None, :], low[None, :, :], high[None, :, :]
            ),
            axis=-1,
        )
    qe = q[:, None, :].astype(jnp.int32)
    lo = low[None, :, :].astype(jnp.int32)
    hi = high[None, :, :].astype(jnp.int32)
    fn = {
        "direct": precision.match_direct,
        "msb_lsb": precision.match_msb_lsb,
        "two_cycle": precision.match_two_cycle,
    }[mode]
    return jnp.all(fn(qe, lo, hi), axis=-1)
