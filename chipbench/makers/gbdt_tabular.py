"""A GBDT trained on one of the repo's Table-II tabular analogs, read from
the file the configuration names.

The configuration's ``model_file`` (``.npz``, under ``chipbench/models/``)
holds the trained tree arrays, the float feature grid (per-feature
quantile cuts fitted on the training split) and a pool of held-out rows
(the analog's valid and test splits).  It was made once by
``python3 chipbench/tools.py train-gas --config <configuration file>``,
which trains with the program's ``train_gbdt`` at the configuration's
rounds, leaves and learning rate.  The benchmark reads only the file, so
the model that a cell scores, and that the reference walks, does not
change with the program's trainer or data generator.  ``n_rounds`` may
keep fewer rounds than the file holds (the tests score the first few).
The grid is handed to the artifact so float rows are binned in the
scoring path.
"""

from __future__ import annotations

import numpy as np

from chipbench import spec

TREE_KEYS = ("feature", "threshold", "left", "right", "value", "tree_out", "node_count")
SCALARS = ("base_score", "n_outputs", "n_features", "n_bins", "n_classes", "task",
           "depth", "n_rounds")


def bin_rows(x: np.ndarray, edges: list[np.ndarray]) -> np.ndarray:
    out = np.empty(x.shape, dtype=np.uint8)
    for f, e in enumerate(edges):
        out[:, f] = np.searchsorted(e, x[:, f], side="right")
    return out


def save(path, made: dict) -> None:
    """Write a model in the layout ``make`` reads (``tools.py train-gas``)."""
    edges = made["edges"]
    np.savez_compressed(
        path, **{k: made[k] for k in TREE_KEYS + SCALARS},
        edge_counts=np.array([len(e) for e in edges]), edges=np.concatenate(edges),
        pool=made["pool"])


def make(cfg: dict) -> dict:
    """The first ``n_rounds`` rounds of the file's model, its grid and pool."""
    with np.load(spec.ROOT / cfg["model_file"]) as z:
        made = {k: z[k] for k in z.files}
    for k in SCALARS:
        made[k] = made[k].item()
    rounds = int(cfg["n_rounds"])
    if not 0 < rounds <= made["n_rounds"]:
        raise ValueError(f"{cfg['model_file']} holds {made['n_rounds']} rounds, "
                         f"not {rounds}")
    keep = rounds * made["n_outputs"]  # a round adds one tree per output
    for k in TREE_KEYS:
        made[k] = made[k][:keep]
    made["n_rounds"] = rounds
    made["edges"] = np.split(made["edges"], np.cumsum(made.pop("edge_counts"))[:-1])
    return made


def rows(trees: dict, data: dict, rng: np.random.Generator, n: int, kind: str) -> np.ndarray:
    pool = data["pool"]
    x = pool[rng.integers(0, pool.shape[0], size=n)]
    if kind == "floats":
        return x
    if kind == "bins":
        return bin_rows(x, data["edges"])
    raise ValueError(f"row kind {kind!r}")
