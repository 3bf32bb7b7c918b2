"""Run one cell of the chip benchmark.

    python3 chipbench/run.py --workload CELL --seed N --seconds S --trace 0|1

from the root of a checkout that holds the program (``src/``), on a
machine with the TPU chips the cell asks for.  Set-up (load or, in a
checkout's first run, build the model; bind; warm every shape the cell
uses) is timed from the process's start; then the window runs for
``--seconds``; then what the window returned is compared with the plain
reference.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with the reference beside its limit, which also end
standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 3
and prints no result; a cell or file that BENCHMARK.json names and that
is missing exits 2.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path


def _process_start() -> float:
    """Epoch seconds at which this process started (Linux), else now."""
    try:
        fields = Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()
        ticks = int(fields[19])  # starttime, field 22 of stat
        boot = next(float(line.split()[1]) for line in
                    Path("/proc/stat").read_text().splitlines()
                    if line.startswith("btime"))
        return boot + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return time.time()


T_START = _process_start()
ROOT = Path(__file__).resolve().parent.parent


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description="run one chip benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness, spec

    try:
        bench = spec.load_benchmark(ROOT)
        cell = spec.resolve(bench, args.workload, ROOT)
    except (spec.SpecError, KeyError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: cell {cell.name!r} needs {cell.chips} TPU chip(s); "
              f"JAX has {len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 3
    result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), t_start=T_START, root=ROOT)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
