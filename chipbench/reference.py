"""The plain reference: a traversal of the ensemble's tree arrays.

It imports nothing of the program and reads nothing the program made:
only the tree arrays and feature edges that the benchmark's makers wrote
(``makers/``).  Trees are stored padded to one node count:

    feature   (T, N) int32    split feature, -1 at a leaf (and padding)
    threshold (T, N) int32    split bin t: go left when bin < t
    left, right (T, N) int32  child node ids
    value     (T, N) float32  leaf value
    tree_out  (T,)   int32    output channel the tree adds into
    node_count (T,) int32    nodes in use per tree (the rest is padding)
    base_score, n_outputs, n_features, n_bins, depth

Float rows are split on the float edge of each bin split: ``bin < t``
is ``x < edges[f][t-1]`` (and always true when the feature has fewer
than t edges), where bin(x) counts the edges at or below x.

Leaves are summed in float64, so the reference carries no rounding
beyond that of the float32 leaves themselves.  ``precision='high'``
instead holds every leaf to what a TPU's three-pass bfloat16 product
keeps (``hi + lo``, about 16 significant bits) and sums in float32: the
lower precision that the control puts in the program's place.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 512


def float_thresholds(trees: dict, edges: list[np.ndarray]) -> np.ndarray:
    """(T, N) float64 thresholds for float rows (``+inf``: always left)."""
    feat = np.maximum(trees["feature"], 0)
    t = trees["threshold"].astype(np.int64) - 1
    out = np.full(feat.shape, np.inf)
    for f, e in enumerate(edges):
        sel = (feat == f) & (trees["feature"] >= 0) & (t < len(e)) & (t >= 0)
        out[sel] = e[t[sel]]
    return out


def leaf_nodes(trees: dict, x: np.ndarray, thresholds: np.ndarray | None = None) -> np.ndarray:
    """(n, T) node id of the leaf each row reaches in each tree."""
    n_trees, n_nodes = trees["feature"].shape
    feature, left, right = (trees[k].ravel() for k in ("feature", "left", "right"))
    thr = (trees["threshold"] if thresholds is None else thresholds).ravel()
    xs = x.astype(np.float64 if thresholds is not None else np.int32)
    base = (np.arange(n_trees, dtype=np.int64) * n_nodes)[None, :]
    row_base = (np.arange(x.shape[0], dtype=np.int64) * x.shape[1])[:, None]
    flat = np.broadcast_to(base, (x.shape[0], n_trees)).copy()  # tree offset + node
    for _ in range(int(trees["depth"])):
        f = feature[flat]
        xv = xs.ravel()[row_base + np.maximum(f, 0)]
        nxt = np.where(xv < thr[flat], left[flat], right[flat]) + base
        flat = np.where(f >= 0, nxt, flat)
    return (flat - base).astype(np.int32)


def round_high(v: np.ndarray) -> np.ndarray:
    """Leaves as a TPU's three-pass bfloat16 product against 1.0 keeps
    them: the high part truncated to bfloat16, the rest rounded to the
    nearest bfloat16 (on a TPU v5e this reads within about 1% of a
    ``Precision.HIGH`` leaf sum over the same rows)."""
    import ml_dtypes

    hi = (v.view(np.uint32) & np.uint32(0xFFFF0000)).view(np.float32)
    lo = (v - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    return hi + lo


def _blocks(n: int, fn) -> None:
    """Run ``fn(start)`` over row blocks; numpy's indexing releases the
    GIL, so blocks run side by side."""
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(fn, range(0, n, BLOCK_ROWS)))


def leaf_values(trees: dict, x: np.ndarray, *, edges: list[np.ndarray] | None = None,
                floats: bool = False) -> np.ndarray:
    """(n, T) float32 value of the leaf each row reaches in each tree."""
    thr = float_thresholds(trees, edges) if floats else None
    tree_ix = np.arange(trees["value"].shape[0])[None, :]
    out = np.empty((x.shape[0], tree_ix.shape[1]), dtype=np.float32)

    def block(s: int) -> None:
        nodes = leaf_nodes(trees, x[s:s + BLOCK_ROWS], thr)
        out[s:s + BLOCK_ROWS] = trees["value"][tree_ix, nodes]

    _blocks(x.shape[0], block)
    return out


def margins(trees: dict, x: np.ndarray, *, edges: list[np.ndarray] | None = None,
            floats: bool = False, precision: str = "exact") -> np.ndarray:
    """(n, n_outputs) margins of rows ``x`` (bins, or floats with edges)."""
    if precision not in ("exact", "high"):
        raise ValueError(f"precision {precision!r}")
    thr = float_thresholds(trees, edges) if floats else None
    value = trees["value"] if precision == "exact" else round_high(trees["value"])
    acc = np.float64 if precision == "exact" else np.float32
    onehot = np.zeros((value.shape[0], int(trees["n_outputs"])), dtype=acc)
    onehot[np.arange(value.shape[0]), trees["tree_out"]] = 1.0
    tree_ix = np.arange(value.shape[0])[None, :]
    out = np.empty((x.shape[0], onehot.shape[1]), dtype=np.float64)

    def block(s: int) -> None:
        nodes = leaf_nodes(trees, x[s:s + BLOCK_ROWS], thr)
        vals = value[tree_ix, nodes].astype(acc)  # (rows, T) leaf values
        out[s:s + BLOCK_ROWS] = vals @ onehot + acc(trees["base_score"])

    _blocks(x.shape[0], block)
    return out
