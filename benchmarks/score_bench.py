"""Offline-scoring benchmark: streamed chunked pipeline vs naive one-shot.

Scores a large pre-binned query block through ``repro.score.score_file``
two ways on identical inputs:

  * **oneshot** — the whole file as a single synchronous chunk
    (``chunk_rows = n_rows``, no double buffer): the naive baseline a
    user gets from ``engine().raw_margin(whole_file)``; its ``(B, R)``
    float32 match intermediate grows with the file (4 GB at the gate
    size) and spills through DRAM;
  * **chunked** — the production pipeline: bounded chunks, one compiled
    bucket, donated double-buffered dispatch; the intermediate stays
    chunk-sized (64 MB) and cache-resident.

Before any timing, the streamed outputs are verified BIT-EQUAL to the
one-shot result — a pipeline that went fast by answering differently
must fail, not record.

The ``speedup`` entry is the ACCEPTANCE GATE (DESIGN.md §14): chunked
must deliver >= ``MIN_SPEEDUP`` x the one-shot rows/s on the gate
config (asserted here), and its ``us_per_call`` carries the inverse
ratio ``1000 / speedup`` — lower is better, like a timing — so the
committed baseline's ``tolerance_pct`` turns a shrinking advantage into
a CI failure the same way a slow kernel is.
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import budget
from repro.api import build
from repro.core.deploy import DeployConfig
from repro.core.trees import random_deep_ensemble
from repro.score import score_file

# the gate config is sized so the one-shot match intermediate (B x R
# float32) is ~4 GB — decisively past cache, the regime the chunked
# pipeline exists for; FULL adds a second shape (wider features,
# smaller table) for the trajectory
GATE = {"n_trees": 128, "depth": 6, "n_features": 16,
        "batch": 131072, "chunk": 2048}
FULL_EXTRA = [
    {"n_trees": 64, "depth": 6, "n_features": 32,
     "batch": 131072, "chunk": 2048},
]
MIN_SPEEDUP = 1.5
# single-core wall clocks drift ~30% run to run (page-cache and
# allocator state); the gate takes the best-of-N min per path and stops
# early once the floor is cleared with margin
GATE_MAX_PAIRS = 3
N_BINS = 256


def _bench_config(cfg: dict) -> list[dict]:
    ens = random_deep_ensemble(
        n_trees=cfg["n_trees"], depth=cfg["depth"],
        n_features=cfg["n_features"], n_bins=N_BINS, seed=20260808,
    )
    cm = build(ens, deploy=DeployConfig(backend="jnp"))
    rng = np.random.default_rng(0)
    q = rng.integers(0, N_BINS, size=(cfg["batch"], cfg["n_features"]))
    q = q.astype(np.int32)
    tag = f"b{cfg['batch']}_r{cm.table.n_rows}_f{cfg['n_features']}"

    def oneshot():
        return score_file(cm, q, kind="margin", chunk_rows=cfg["batch"],
                          double_buffer=False)

    def chunked():
        return score_file(cm, q, kind="margin", chunk_rows=cfg["chunk"])

    # first runs compile each bucket's jit entry AND pin bit-equality
    ref, stream = oneshot(), chunked()
    if not np.array_equal(stream.values, ref.values):
        raise AssertionError(f"streamed != one-shot at {tag}")
    # timed runs (engine bindings warm): min elapsed per path across up
    # to GATE_MAX_PAIRS interleaved pairs, stopping once the gate
    # clears the floor with 10% margin — the min is the stable estimate
    # under single-core wall-clock drift
    one, chk = oneshot(), chunked()
    one_s, chk_s = one.elapsed_s, chk.elapsed_s
    for _ in range(GATE_MAX_PAIRS - 1):
        if cfg != GATE or one_s / chk_s >= MIN_SPEEDUP * 1.1:
            break
        o2, c2 = oneshot(), chunked()
        one_s = min(one_s, o2.elapsed_s)
        chk_s = min(chk_s, c2.elapsed_s)
    one_rows = one.n_rows / one_s
    chk_rows = chk.n_rows / chk_s
    speedup = one_s / chk_s
    rows = [
        {
            "name": f"score/oneshot_{tag}",
            "us_per_call": one_s * 1e6,
            "derived": (
                f"rows_per_s={one_rows:,.0f};chunks={one.n_chunks};"
                f"kernel={one.engine['kernel']};bits_equal=True"
            ),
            "config": {**cfg, "kind": "margin", "double_buffer": False},
        },
        {
            "name": f"score/chunked_{tag}",
            "us_per_call": chk_s * 1e6,
            "derived": (
                f"rows_per_s={chk_rows:,.0f};chunks={chk.n_chunks};"
                f"bucket={chk.bucket};speedup_vs_oneshot={speedup:.2f}"
            ),
            "config": {**cfg, "kind": "margin", "double_buffer": True},
        },
    ]
    if cfg == GATE:
        if speedup < MIN_SPEEDUP:
            raise AssertionError(
                f"chunked pipeline speedup {speedup:.2f}x below the "
                f"{MIN_SPEEDUP}x acceptance floor at {tag} "
                f"(oneshot {one_rows:,.0f} rows/s, "
                f"chunked {chk_rows:,.0f} rows/s)"
            )
        rows.append({
            # gate row: us_per_call is 1000/speedup (lower = better),
            # so the baseline tolerance_pct gates advantage loss
            "name": f"speedup_{tag}",
            "us_per_call": 1000.0 / speedup,
            "derived": (
                f"gate=chunked_speedup;speedup={speedup:.2f};"
                f"floor={MIN_SPEEDUP};"
                f"chunked_rows_per_s={chk_rows:,.0f}"
            ),
            "config": {**cfg, "kind": "margin"},
        })
    return rows


def run() -> list[dict]:
    rows: list[dict] = []
    for cfg in ([GATE] if budget(0, 1) else [GATE] + FULL_EXTRA):
        rows.extend(_bench_config(cfg))
    return rows


if __name__ == "__main__":
    for r in run():
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
