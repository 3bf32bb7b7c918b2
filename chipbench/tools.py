"""Measurements made once, by hand, on the chip; the benchmark's runs never
call these.

    python3 chipbench/tools.py sweep --workload CELL --rates 1000,2000 \\
        --seconds 5 --seed N
        The open loop at each offered rate in one process (set up once;
        a rate listed twice gets two windows on different seeds):
        p50/p99, shed, how late the generator ran, and whether the
        backlog grew (the median latency of the last tenth of requests
        over that of the first tenth).  The last line gives the knee, the
        highest rate with nothing shed or lost and no growing backlog
        (``knee_per_s``), and 4/5 of it, the rate a cell offers
        (``rate_per_s``).

    python3 chipbench/tools.py control --workload CELL --seeds A,B,C \\
        --seconds S
        For each seed, a short window at the cell's own load, then on the
        same rows: the program's margin_err (a lower reading) and the
        control's: the reference with every leaf held to what a
        three-pass bfloat16 product keeps ('high', numpy), and the same
        leaf sum on the TPU at Precision.HIGH and at DEFAULT.

    python3 chipbench/tools.py train-gas --config chipbench/configs/gas-gbdt.json
        Makes the configuration's ``model_file``: fits the float grid
        (per-feature quantile cuts of the training split), bins the
        training split with it and trains with the program's
        ``train_gbdt`` at the configuration's rounds, leaves, depth and
        learning rate on the repo's analog of the dataset; the pool of
        rows is the analog's valid and test splits.  The committed file
        is what the benchmark scores: run this only to make a new one.

Sweep and control print one JSON line per rate or seed on standard output.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent


def _session(cell, seed: int, seconds: float):
    from chipbench import artifact, harness, spec

    harness.enable_compile_cache()
    model, _ = artifact.load_or_build(cell.config, cell.config_path)
    workdir = harness.Workdir(artifact.CACHE / "run" / f"{cell.name}-tools")
    drv = spec.driver(cell.traffic, ROOT)
    session = drv.Session(model, cell.traffic, harness.seed_streams(seed), workdir, seconds)
    gc.freeze()  # as run_cell does at the end of set-up
    return model, session, workdir


def knee(windows: list[dict], max_growth: float = 2.0) -> float:
    """The highest swept rate at which every window, and every window at
    each lower rate, shed and lost nothing and saw no growing backlog."""
    best = 0.0
    for rate in sorted({w["rate_per_s"] for w in windows}):
        at = [w for w in windows if w["rate_per_s"] == rate]
        if any(w["failed"] or not w["backlog_growth"] < max_growth for w in at):
            break
        best = rate
    return best


def sweep(cell, rates: list[float], seconds: float, seed: int) -> None:
    from chipbench import harness

    model, session, workdir = _session(cell, seed, seconds)
    windows = []
    try:
        for i, rate in enumerate(rates):
            session.traffic = dict(cell.traffic, rate_per_s=rate)
            session.reseed(harness.seed_streams(seed + i + 1), seconds)
            before = session.counters()
            out = session.window(seconds)
            after = session.counters()
            lat = session.last_latencies_ms
            k = max(1, len(lat) // 10)
            growth = (float(np.median(lat[-k:]) / np.median(lat[:k]))
                      if np.isfinite(lat).all() else float("inf"))
            windows.append({
                "rate_per_s": rate, **out.metrics, "failed": out.failed,
                "attempted": out.attempted, "backlog_growth": growth,
                "rows_per_flush": (after["served_rows"] - before["served_rows"])
                / max(1, after["flushes"] - before["flushes"]),
                "served_rows_per_s": out.rows_done / out.window_s,
                "notes": out.notes})
            print(json.dumps(windows[-1]), flush=True)
    finally:
        session.close()
        workdir.remove()
    k = knee(windows)
    print(json.dumps({"knee_per_s": k, "rate_per_s": 0.8 * k}), flush=True)


def control(cell, seeds: list[int], seconds: float) -> None:
    import jax
    import jax.numpy as jnp

    from chipbench import harness, reference

    model, session, workdir = _session(cell, seeds[0], seconds)
    onehot = np.zeros((model.trees["value"].shape[0], int(model.trees["n_outputs"])),
                      np.float32)
    onehot[np.arange(onehot.shape[0]), model.trees["tree_out"]] = 1.0
    lo, hi = [], []
    try:
        for i, seed in enumerate(seeds):
            if i:
                session.reseed(harness.seed_streams(seed), seconds)
            out = session.window(seconds)
            pairs = harness.reference_pairs(model, out)
            row = {"seed": seed, "program": harness.margin_err(pairs),
                   "missing": out.never_came}
            high = harness.reference_pairs(model, out, precision="high")
            row["control_high"] = harness.margin_err(
                [(ref, h) for (ref, _), (h, _) in zip(pairs, high)])
            for prec in ("HIGH", "DEFAULT"):
                gots = []
                for x, _ in out.compared:
                    vals = reference.leaf_values(model.trees, x, edges=model.data.get("edges"),
                                                 floats=out.floats)
                    dev = jnp.dot(jnp.asarray(vals), jnp.asarray(onehot),
                                  precision=getattr(jax.lax.Precision, prec))
                    gots.append(np.asarray(dev, np.float64) + float(model.trees["base_score"]))
                row[f"control_tpu_{prec.lower()}"] = harness.margin_err(
                    [(ref, g) for (ref, _), g in zip(pairs, gots)])
            lo.append(row["program"])
            hi.append(min(row["control_high"], row["control_tpu_high"]))
            print(json.dumps(row), flush=True)
        print(json.dumps({"lower_reading": max(lo), "upper_reading": min(hi),
                          "ratio": min(hi) / max(lo) if max(lo) > 0 else None}))
    finally:
        session.close()
        workdir.remove()


def fit_edges(x: np.ndarray, n_bins: int) -> list[np.ndarray]:
    """Per-feature ascending quantile cut points (at most n_bins - 1)."""
    qs = np.linspace(0, 1, n_bins + 1)[1:-1]
    edges = []
    for f in range(x.shape[1]):
        col = x[:, f][np.isfinite(x[:, f])]
        e = np.unique(np.quantile(col, qs))
        edges.append(e[(e > col.min()) & (e <= col.max())].astype(np.float64))
    return edges


def train_gas(cfg_path: Path) -> None:
    from repro.core.trees import GBDTParams, train_gbdt
    from repro.data.tabular import make_dataset

    from chipbench import work
    from chipbench.makers import gbdt_tabular

    cfg = json.loads(cfg_path.read_text())
    ds = make_dataset(cfg["dataset"], seed=int(cfg["seed"]))
    n_bins = int(cfg["n_bins"])
    edges = fit_edges(ds.x_train, n_bins)
    ens = train_gbdt(
        gbdt_tabular.bin_rows(ds.x_train, edges), ds.y_train, task=ds.task, n_bins=n_bins,
        n_classes=ds.n_classes,
        params=GBDTParams(n_rounds=int(cfg["n_rounds"]), max_leaves=int(cfg["max_leaves"]),
                          max_depth=int(cfg["max_depth"]),
                          learning_rate=float(cfg["learning_rate"])),
    )
    n_nodes = max(t.n_nodes for t in ens.trees)
    t = len(ens.trees)
    made = {k: np.zeros((t, n_nodes), dtype=dt) for k, dt in (
        ("threshold", np.int32), ("left", np.int32), ("right", np.int32),
        ("value", np.float32))}
    made["feature"] = np.full((t, n_nodes), -1, dtype=np.int32)
    for i, tree in enumerate(ens.trees):
        for name in ("feature", "threshold", "left", "right", "value"):
            made[name][i, :tree.n_nodes] = getattr(tree, name)
    made.update(
        tree_out=np.asarray(ens.tree_class, dtype=np.int32),
        node_count=np.array([tr.n_nodes for tr in ens.trees], dtype=np.int32),
        base_score=np.float32(ens.base_score), n_outputs=int(ens.n_outputs),
        n_features=int(ens.n_features), n_bins=n_bins, n_classes=int(ens.n_classes),
        task=ens.task, depth=max(tree.max_depth for tree in ens.trees),
        n_rounds=int(cfg["n_rounds"]), edges=edges,
        pool=np.concatenate([ds.x_valid, ds.x_test]).astype(np.float32))
    out = ROOT / cfg["model_file"]
    out.parent.mkdir(parents=True, exist_ok=True)
    gbdt_tabular.save(out, made)
    print(json.dumps({"model_file": cfg["model_file"], "trees": t,
                      "leaves": work.sizes_of(made).leaves,
                      "pool_rows": int(made["pool"].shape[0])}), flush=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("tool", choices=("sweep", "control", "train-gas"))
    ap.add_argument("--workload")
    ap.add_argument("--config", help="train-gas: the configuration file")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import spec

    if args.tool == "train-gas":
        if not args.config:
            ap.error("train-gas needs --config")
        train_gas(ROOT / args.config)
        return 0
    if not args.workload:
        ap.error(f"{args.tool} needs --workload")
    cell = spec.resolve(spec.load_benchmark(ROOT), args.workload, ROOT)
    t0 = time.time()
    if args.tool == "sweep":
        sweep(cell, [float(r) for r in args.rates.split(",")], args.seconds, args.seed)
    else:
        control(cell, [int(s) for s in args.seeds.split(",")], args.seconds)
    print(f"[tools] {args.tool} done in {time.time() - t0:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
