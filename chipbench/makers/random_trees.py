"""Complete random trees with float32 leaves, from the configuration's seed.

Every tree is complete to ``depth`` (2**depth leaves), splits on a
feature drawn uniformly from all features (``p_dup`` 0: a split never
re-draws a feature of its own path on purpose, so no path is built to
contradict itself, as no trainer emits such paths) at a bin threshold
drawn from [1, n_bins).  Leaves are float32 draws from N(0, leaf_std²);
any draw that bfloat16 holds exactly is nudged by one float32 ulp, so no
leaf survives a cast to bfloat16 unchanged.  Tree i adds into output
i mod n_outputs.  Rows are uniform over the bins.
"""

from __future__ import annotations

import numpy as np


def make(cfg: dict) -> dict:
    """Tree arrays in the layout ``reference.py`` reads."""
    if cfg.get("p_dup", 0.0) != 0.0:
        raise ValueError("random_trees draws path features independently: p_dup must be 0")
    t, d = int(cfg["n_trees"]), int(cfg["depth"])
    n_feat, n_bins = int(cfg["n_features"]), int(cfg["n_bins"])
    n_out = int(cfg["n_classes"]) if cfg["task"] == "multiclass" else 1
    rng = np.random.default_rng(int(cfg["seed"]))
    n_inner, n_leaf = 2 ** d - 1, 2 ** d
    n_nodes = n_inner + n_leaf
    feature = np.full((t, n_nodes), -1, dtype=np.int32)
    threshold = np.zeros((t, n_nodes), dtype=np.int32)
    feature[:, :n_inner] = rng.integers(0, n_feat, size=(t, n_inner))
    threshold[:, :n_inner] = rng.integers(1, n_bins, size=(t, n_inner))
    inner = np.arange(n_inner, dtype=np.int32)
    left = np.zeros((t, n_nodes), dtype=np.int32)
    right = np.zeros((t, n_nodes), dtype=np.int32)
    left[:, :n_inner] = 2 * inner + 1  # heap layout: children of j are 2j+1, 2j+2
    right[:, :n_inner] = 2 * inner + 2
    value = np.zeros((t, n_nodes), dtype=np.float32)
    leaves = rng.normal(0.0, float(cfg["leaf_std"]), size=(t, n_leaf)).astype(np.float32)
    exact = (leaves.view(np.uint32) & 0xFFFF) == 0
    leaves[exact] = np.nextafter(leaves[exact], np.float32(np.inf))
    value[:, n_inner:] = leaves
    return {
        "feature": feature, "threshold": threshold, "left": left,
        "right": right, "value": value,
        "tree_out": (np.arange(t) % n_out).astype(np.int32),
        "base_score": np.float32(cfg.get("base_score", 0.0)),
        "n_outputs": n_out, "n_features": n_feat, "n_bins": n_bins,
        "n_classes": int(cfg.get("n_classes", 1)), "task": cfg["task"],
        "depth": d, "node_count": np.full(t, n_nodes, dtype=np.int32),
    }


def rows(trees: dict, data: dict, rng: np.random.Generator, n: int, kind: str) -> np.ndarray:
    if kind != "bins":
        raise ValueError("random_trees rows are bins; this configuration has no float grid")
    return rng.integers(0, int(trees["n_bins"]), size=(n, int(trees["n_features"])),
                        dtype=np.uint8)
