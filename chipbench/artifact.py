"""Build a configuration's model once per checkout, then load it.

The first run of a configuration in a checkout (the cold run) calls the
configuration's maker, builds the program's ``CompiledModel`` from the
tree arrays (``DeployConfig(backend='pallas')``, defaults otherwise) and
saves both under ``chipbench/.cache/models/<config>-<seed>-<hash>``,
where the hash covers the configuration file, the maker's source and
the model file that the configuration names, if any.
The directory is written under a temporary name and renamed into place,
so a run that dies half way leaves nothing that a later run would load.
Every later run loads it: what a deployment pays on a restart.
"""

from __future__ import annotations

import hashlib
import os
import shutil
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from chipbench import spec

CACHE = spec.HERE / ".cache"

@dataclass
class Model:
    config: dict
    trees: dict  # reference.py's tree arrays
    data: dict  # row material for the maker: "edges", "pool" where it has them
    compiled: object  # repro.api.CompiledModel
    maker: object  # the maker module (rows())

    def rows(self, rng: np.random.Generator, n: int, kind: str) -> np.ndarray:
        return self.maker.rows(self.trees, self.data, rng, n, kind)


def _key(cfg_path: Path, maker_path: Path, cfg: dict) -> str:
    h = hashlib.sha256(cfg_path.read_bytes() + maker_path.read_bytes())
    if "model_file" in cfg:
        h.update((spec.ROOT / cfg["model_file"]).read_bytes())
    return f"{cfg['name']}-{cfg['seed']}-{h.hexdigest()[:12]}"


def to_ensemble(trees: dict):
    """The program's ``Ensemble`` over the same tree arrays."""
    from repro.core.trees import Ensemble, Tree

    return Ensemble(
        trees=[Tree(feature=trees["feature"][i, :k].copy(),
                    threshold=trees["threshold"][i, :k].copy(),
                    left=trees["left"][i, :k].copy(),
                    right=trees["right"][i, :k].copy(),
                    value=trees["value"][i, :k].copy())
               for i, k in enumerate(trees["node_count"])],
        n_features=int(trees["n_features"]), n_bins=int(trees["n_bins"]),
        task=str(trees["task"]), kind="gbdt", n_classes=int(trees["n_classes"]),
        tree_class=trees["tree_out"].astype(np.int32),
        base_score=float(trees["base_score"]), leaf_class_mode="tree",
    )


def _save(made: dict, cfg: dict, out: Path) -> None:
    from repro.api import build
    from repro.core.deploy import DeployConfig
    from repro.core.quantize import FeatureQuantizer

    edges = made.pop("edges", None)
    pool = made.pop("pool", None)
    quantizer = (None if edges is None else
                 FeatureQuantizer(edges=list(edges), n_bins=int(made["n_bins"])))
    cm = build(to_ensemble(made), deploy=DeployConfig(backend="pallas"),
               quantizer=quantizer)
    cm.save(out / "model")
    data = {}
    if edges is not None:
        data["edge_counts"] = np.array([len(e) for e in edges])
        data["edges"] = np.concatenate(edges) if edges else np.zeros(0)
    if pool is not None:
        data["pool"] = pool
    np.savez(out / "trees.npz", **made, **{f"data_{k}": v for k, v in data.items()})


def _load_arrays(path: Path) -> tuple[dict, dict]:
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    # scalars of the bundle were saved as 0-d arrays
    trees = {k: v.item() if v.ndim == 0 else v for k, v in arrays.items()
             if not k.startswith("data_")}
    data = {k[5:]: v for k, v in arrays.items() if k.startswith("data_")}
    if "edges" in data:
        data["edges"] = np.split(data["edges"], np.cumsum(data.pop("edge_counts"))[:-1])
    return trees, data


def load_or_build(cfg: dict, cfg_path: Path, cache: Path = CACHE) -> tuple[Model, bool]:
    """The configuration's model, and whether this call built it."""
    from repro.api import CompiledModel

    maker = spec.maker(cfg)
    final = cache / "models" / _key(cfg_path, Path(maker.__file__), cfg)
    built = False
    if not (final / "trees.npz").is_file():
        tmp = final.with_name(final.name + f".tmp{os.getpid()}")
        shutil.rmtree(tmp, ignore_errors=True)
        tmp.mkdir(parents=True)
        _save(maker.make(cfg), cfg, tmp)
        try:
            os.rename(tmp, final)
        except OSError:  # another process got there first: keep its copy
            shutil.rmtree(tmp, ignore_errors=True)
        built = True
    trees, data = _load_arrays(final / "trees.npz")
    compiled = CompiledModel.load(final / "model")
    return Model(cfg, trees, data, compiled, maker), built


def describe(model: Model) -> str:
    t = model.trees
    return (f"{model.config['name']}: {t['feature'].shape[0]} trees, depth "
            f"{t['depth']}, {model.compiled.table.n_rows} CAM rows, "
            f"{t['n_features']} features, {t['n_outputs']} outputs")
