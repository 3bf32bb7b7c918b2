"""The model's work, counted from the ensemble and never from the table.

A CAM engine scores a row by comparing it with every leaf's path and
adding the matched leaves into the outputs.  Let L be the ensemble's
leaves, F its features and C its outputs:

    ops per scored row = L * (2F + 2C)
        two range compares per (leaf, feature), one multiply-add
        (two ops) per (leaf, output);
    bytes per call of N rows = L*F*2*b + L*C*4 + N*F*b + N*C*4
        the low and high bound of every (leaf, feature) at b bytes per
        bin, the float32 leaf values, the N query rows in, the N*C
        float32 margins out.

Padding, compression, tile skipping, the table dtype and batch tiling
change none of these numbers, so every version of the engine is read
against the same work.  The bins are 8-bit, so the op peak is the int8
one; the VPU's compare rate is not published, which makes every share
read low and keeps it under 100%.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


@dataclass(frozen=True)
class ModelSizes:
    leaves: int  # L
    features: int  # F
    outputs: int  # C
    n_bins: int

    @property
    def bin_bytes(self) -> int:
        return 1 if self.n_bins <= 256 else 2 if self.n_bins <= 65536 else 4

    @property
    def ops_per_row(self) -> int:
        return self.leaves * (2 * self.features + 2 * self.outputs)

    def bytes_per_call(self, n_rows: int) -> int:
        b = self.bin_bytes
        return (self.leaves * self.features * 2 * b + self.leaves * self.outputs * 4
                + n_rows * self.features * b + n_rows * self.outputs * 4)


def sizes_of(trees: dict) -> ModelSizes:
    """Sizes from the ensemble's tree arrays (see ``reference.py``)."""
    return ModelSizes(
        leaves=int(((trees["feature"] < 0)
                    & (np.arange(trees["feature"].shape[1])
                       < trees["node_count"][:, None])).sum()),
        features=int(trees["n_features"]),
        outputs=int(trees["n_outputs"]),
        n_bins=int(trees["n_bins"]),
    )


def peaks_for(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {path.name}; known: {sorted(table)}")
    return table[device_kind]


def least_time_s(sizes: ModelSizes, call_rows: list[int], peaks: dict) -> float:
    """Least time the chip could take for these kernel calls: per call the
    larger of its ops over the op peak and its bytes over the bandwidth."""
    return sum(max(n * sizes.ops_per_row / peaks["int8_ops_per_s"],
                   sizes.bytes_per_call(n) / peaks["hbm_bytes_per_s"])
               for n in call_rows)


def ridge_ops_per_byte(peaks: dict) -> float:
    return peaks["int8_ops_per_s"] / peaks["hbm_bytes_per_s"]


def kernel_roofline_pct(rec, kernel: str) -> float | None:
    """Least time for the model's work of every call of ``kernel`` in the
    traced window over the summed device time of its events, in percent.
    Needs one event per call that the window made; otherwise None."""
    from chipbench import trace_reduce

    if rec.trace is None:
        return None
    seconds, events = trace_reduce.kernel_time(rec.trace, kernel)
    calls = rec.outcome.kernel_call_rows
    if events == 0 or events != len(calls) or seconds <= 0:
        return None
    return 100.0 * least_time_s(rec.sizes, calls, rec.peaks) / seconds


def mfu_pct(rec) -> float | None:
    """Model ops of the rows the window completed over window seconds x
    chips x the int8 op peak, in percent."""
    if rec.outcome.window_s <= 0 or not rec.outcome.rows_done:
        return None
    ops = rec.outcome.rows_done * rec.sizes.ops_per_row
    return 100.0 * ops / (rec.outcome.window_s * rec.chips * rec.peaks["int8_ops_per_s"])
