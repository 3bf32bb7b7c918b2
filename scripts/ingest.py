"""Convert a serialized model dump into a saved X-TIME CompiledModel.

    python scripts/ingest.py model.json --out artifacts/churn
    python scripts/ingest.py model.txt  --out artifacts/lgbm --n-bins 256
    python scripts/ingest.py model.json --out a/m --expected golden.json

Ingests an XGBoost-JSON / LightGBM-text / sklearn-forest dump with the
zero-dependency parsers in ``repro.ingest`` (the source libraries are
never imported), lowers it onto the threshold grid, compiles + places it
(``repro.api.build``), prints the lowering report, and writes the
``<out>.npz`` + ``<out>.json`` artifact a serve process cold-starts from
(``TableRegistry.register(name, CompiledModel.load(out))``).

``--expected`` verifies the saved artifact end-to-end: the recorded
float queries are binned with the artifact's grid and served through the
engine; raw margins and predictions must match the recorded reference
bit-exactly (exit 1 otherwise) — the CI ``ingest-golden`` job runs this
over every checked-in fixture.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from _cli import verify_expected  # noqa: E402,F401  (bootstraps src/)

from repro.ingest import FORMATS, IngestError, load_model  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dump", help="model dump (XGBoost .json / LightGBM .txt / "
                                 "sklearn-forest .json)")
    ap.add_argument("--out", required=True, metavar="BASE",
                    help="artifact base path (writes BASE.npz + BASE.json)")
    ap.add_argument("--format", default="auto",
                    choices=("auto",) + FORMATS)
    ap.add_argument("--n-bins", type=int, default=256,
                    help="threshold grid size (default: %(default)s — the "
                         "paper's 8-bit grid)")
    ap.add_argument("--strict", action="store_true",
                    help="reject models whose thresholds do not fit the grid "
                         "instead of merging (merging loses bit-exactness)")
    ap.add_argument("--batching", action="store_true",
                    help="build the §III-D input-batching router program")
    ap.add_argument("--compress", default="off", metavar="LEVEL",
                    help="CAM table compression level (off/prune/merge/full/"
                         "auto, default: %(default)s) — bit-equivalent row "
                         "merging + pruning, see repro.core.compress")
    ap.add_argument("--expected", metavar="JSON",
                    help="golden reference {x, raw_margin, predict}; verify "
                         "the saved artifact serves it bit-exactly")
    args = ap.parse_args(argv)

    from repro.api import CompiledModel, build  # lazy: --help stays instant
    from repro.core.deploy import DeployConfig
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    try:
        imported = load_model(args.dump, format=args.format)
        artifact = build(
            imported,
            deploy=DeployConfig(batching=args.batching),
            n_bins=args.n_bins,
            on_overflow="raise" if args.strict else "merge",
            compress=args.compress,
        )
    except (IngestError, ValueError) as e:
        print(f"[ingest]  ERROR: {e}", file=sys.stderr)
        return 1

    rep = artifact.ingest or {}
    print(f"[ingest]  {imported.source} ({imported.source_kind}, "
          f"{imported.task}): {rep.get('n_source_trees')} trees -> "
          f"{rep.get('n_trees')} lowered, {artifact.table.n_rows} CAM rows")
    grid = [g for g in rep.get("grid", ()) if g["thresholds"]]
    peak = max((g["thresholds"] for g in grid), default=0)
    print(f"[grid]    {len(grid)}/{rep.get('n_features')} features split, "
          f"peak {peak}/{args.n_bins - 1} edges, "
          f"exact={rep.get('exact')} "
          f"(merged={rep.get('merged_thresholds')}, "
          f"remapped={rep.get('remapped_splits')})")
    for note in rep.get("notes", ()):
        print(f"[note]    {note}")
    if artifact.compression is not None:
        c = artifact.compression
        print(f"[compress] level '{c['level']}': {c['rows_before']} -> "
              f"{c['rows_after']} rows ({c['row_savings_fraction']:.0%} saved; "
              f"pruned {c['pruned_empty'] + c['pruned_unreachable']}, "
              f"merged {c['merged_rows']}, "
              f"{c['cols_before'] - c['cols_after']} columns collapsed)")
    print(f"[place]   {artifact.placement.n_cores_used} cores, "
          f"replication x{artifact.placement.replication}, "
          f"NoC '{artifact.noc.config}', "
          f"{artifact.table.feature_occupancy().mean():.0%} of CAM cells "
          "non-wildcard")

    sidecar = artifact.save(args.out)
    print(f"[save]    {sidecar} (+ .npz)")

    if args.expected:
        reloaded = CompiledModel.load(args.out)  # verify the DISK artifact
        return verify_expected(reloaded, Path(args.expected))
    return 0


if __name__ == "__main__":
    sys.exit(main())
