"""Streaming batch scoring: saved artifact × columnar file → predictions.

The throughput counterpart to the latency-focused serve tiers (DESIGN.md
§6/§12): bulk offline scoring of columnar rows — the `tact`-style BDT
analysis workload — at maximum rows/s.  The pipeline (DESIGN.md §14):

    read chunk i+1 ──┐ host: mmap slice → grid binning → bucket pad
                     │
    score chunk i  ──┤ device: donated transfer → batch-hinted kernel
                     │
    drain chunk i-1 ─┘ host: fetch outputs → streaming .npy writer

Three structural wins over naive whole-file one-shot scoring:

  * **bounded working set** — the kernel's ``(B, R)`` match intermediate
    stays chunk-sized and cache/VMEM-resident instead of growing with
    the file (a one-shot over 10⁵+ rows spills multi-GB intermediates
    through DRAM; over 10⁹ rows it simply does not fit);
  * **donated double-buffering** — chunk ``i``'s query buffer is donated
    to the device (``padded_fn``) while the host bins chunk ``i+1`` and
    drains chunk ``i-1``, so host→device transfer overlaps compute and
    at most two chunks are in flight;
  * **one compiled shape** — every chunk (tail included) pads to one
    bucket, so the whole file runs through a single jit entry, bound via
    ``CompiledModel.engine(batch_hint=...)`` so a tuned artifact's
    dispatch table picks the measured-best kernel for that bucket.

Each stage of a chunk (read, bin, pad, dispatch, wait, fetch, write) and
the engine's binding run under an ``xtime.score.<stage>`` profiler span
(``repro.obs``), so a trace charges the device's idle time to a stage.

Bit-equivalence contract: every CAM row match and leaf accumulation is
per-query-row independent, so the concatenated streamed outputs are
BIT-IDENTICAL to a single ``predict``/``raw_margin`` call over the whole
file with the same engine configuration — across chunk sizes, tails,
double-buffering on/off, and the mesh ``batch`` NoC program
(tests/test_score.py).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from repro.obs import span
from repro.score.reader import open_columnar
from repro.score.writer import PredictionWriter

#: what ``kind`` selects — engine margins (the BDT analysis score) or
#: final predictions (argmax/sign/regression value)
KINDS = ("margin", "predict")


@dataclass(frozen=True)
class ScoreResult:
    """One streaming scoring run: the outputs plus its throughput record."""

    values: np.ndarray  # (n_rows, n_outputs) margins or (n_rows,) predictions
    path: Path | None  # where values were streamed (None: in-memory)
    kind: str
    n_rows: int
    n_features: int
    n_chunks: int
    chunk_rows: int
    bucket: int  # padded per-chunk batch (one jit entry for the whole file)
    binned: bool  # True when the artifact's grid binned float input
    double_buffered: bool
    elapsed_s: float
    engine: dict = field(default_factory=dict)  # bound-engine provenance

    @property
    def rows_per_s(self) -> float:
        if self.elapsed_s <= 0:
            return 0.0
        return self.n_rows / self.elapsed_s


def _load_model(model):
    from repro.api import CompiledModel  # numpy-only import

    if isinstance(model, (str, Path)):
        return CompiledModel.load(model)
    if not isinstance(model, CompiledModel):
        raise TypeError(
            "score_file takes a CompiledModel or a saved-artifact path, "
            f"got {type(model).__name__}"
        )
    return model


def _empty_tail(model, kind: str) -> tuple[tuple, np.dtype]:
    """Output (trailing shape, dtype) for a zero-row input, mirroring the
    engine's own output contract without binding an engine."""
    if kind == "margin":
        return (int(model.table.n_outputs),), np.dtype(np.float32)
    if model.table.task == "regression":
        return (), np.dtype(np.float32)
    return (), np.dtype(np.int32)


def _drain(writer: PredictionWriter, start: int, n: int, dev) -> None:
    """Wait for one chunk's outputs, fetch its ``n`` real rows, write them."""
    with span("xtime.score.wait"):
        dev.block_until_ready()
    with span("xtime.score.fetch"):
        out = np.asarray(dev)[:n]
    with span("xtime.score.write"):
        writer.write(start, out)


def score_file(
    model,
    source,
    *,
    kind: str = "margin",
    chunk_rows: int = 8192,
    out: str | Path | None = None,
    mesh=None,
    columns: list[str] | None = None,
    double_buffer: bool = True,
    **overrides,
) -> ScoreResult:
    """Stream ``source`` through ``model``'s engine chunk by chunk.

    Args:
      model: a ``CompiledModel`` or a saved-artifact base path.
      source: 2-D ndarray, ``.npy`` path (memory-mapped), ``.parquet``
        path (optional pyarrow), or an open reader source.  Float rows
        are binned chunk-by-chunk with the artifact's attached grid
        (``CompiledModel.quantizer``); integer rows are treated as
        already-binned queries and pass the grid by.
      kind: 'margin' (raw per-channel scores) or 'predict' (final
        predictions) — same outputs as ``XTimeEngine.raw_margin`` /
        ``predict`` over the whole file, bit for bit.
      chunk_rows: rows per chunk; the actual device batch is the
        ``bucket`` this pads to (engine tiling × mesh divisibility).
      out: optional ``.npy`` path to stream predictions into
        (preallocated memmap — bounded memory at any file size).
      mesh: optional jax Mesh; chunks then fan out under the ``batch``
        NoC program (replicated tables, zero cross-device collectives)
        unless ``overrides`` names another ``noc_config``.
      double_buffer: keep one chunk in flight while the host prepares
        the next (the donated-overlap pipeline).  ``False`` drains every
        chunk synchronously — same bits, no overlap (debug/measure).
      overrides: ``DeployConfig`` field updates for the engine binding.

    Returns a :class:`ScoreResult`; ``.values`` is the full output array
    (memmap-backed when ``out`` was given).
    """
    if kind not in KINDS:
        raise ValueError(f"kind {kind!r} not in {KINDS}")
    if chunk_rows < 1:
        raise ValueError(f"chunk_rows must be >= 1, got {chunk_rows}")
    model = _load_model(model)
    src = open_columnar(source, columns=columns)
    try:
        n_rows, n_feat = src.n_rows, src.n_features
        expect = int(model.table.n_features)
        if n_feat != expect:
            raise ValueError(
                f"input has {n_feat} feature columns, the artifact expects "
                f"{expect}"
            )
        needs_grid = np.dtype(src.dtype).kind not in "iu"
        if needs_grid and model.quantizer is None:
            raise ValueError(
                "float columnar input needs the artifact's feature grid to "
                "bin queries, but this artifact has none attached; build "
                "with quantizer=... (or from an ingested dump), or provide "
                "already-binned integer rows"
            )
        writer = PredictionWriter(n_rows, path=out)
        if n_rows == 0:
            # a valid (empty) scoring run; never touches jax
            values = writer.finalize(empty_like=_empty_tail(model, kind))
            return ScoreResult(
                values=values, path=writer.path, kind=kind, n_rows=0,
                n_features=n_feat, n_chunks=0, chunk_rows=chunk_rows,
                bucket=0, binned=needs_grid, double_buffered=double_buffer,
                elapsed_s=0.0, engine={},
            )

        from repro.kernels import ops as kops  # lazy: touches jax
        from repro.core.tune import kernel_version

        with span("xtime.score.bind"):
            engine = model.engine(mesh=mesh, batch_hint=chunk_rows, **(
                {"noc_config": "batch", **overrides}
                if mesh is not None and "noc_config" not in overrides
                else overrides
            ))
        # one bucket for every chunk (tail included): a single jit entry,
        # sized to what both the kernel tiling and the mesh accept
        mult = int(np.lcm(engine.b_blk, engine.batch_multiple))
        bucket = int(np.ceil(min(chunk_rows, n_rows) / mult)) * mult
        run = engine.padded_fn(kind)
        quantizer = model.quantizer

        t0 = time.perf_counter()
        pending: tuple[int, int, object] | None = None
        n_chunks = 0
        chunks = src.iter_chunks(chunk_rows)
        while True:
            with span("xtime.score.read"):  # the slice and its copy
                item = next(chunks, None)
            if item is None:
                break
            start, chunk = item
            if needs_grid:
                with span("xtime.score.bin"):
                    bins = quantizer.transform(chunk)
            else:
                bins = chunk
            with span("xtime.score.pad"):
                q = kops.pad_to_bucket(
                    engine.select_features(np.asarray(bins)),
                    bucket, engine.arrays.f_pad, dtype=engine.table_dtype,
                )
            # dispatch is async: the device starts on this chunk (its
            # query buffer donated) while the host drains the previous
            # one and reads/bins the next — at most two chunks in flight
            with span("xtime.score.dispatch"):
                dev = run(q)
            n_chunks += 1
            if pending is not None:
                _drain(writer, *pending)
            if double_buffer:
                pending = (start, chunk.shape[0], dev)
            else:
                _drain(writer, start, chunk.shape[0], dev)
        if pending is not None:
            _drain(writer, *pending)
        values = writer.finalize()
        elapsed = time.perf_counter() - t0

        return ScoreResult(
            values=values, path=writer.path, kind=kind, n_rows=n_rows,
            n_features=n_feat, n_chunks=n_chunks, chunk_rows=chunk_rows,
            bucket=bucket, binned=needs_grid, double_buffered=double_buffer,
            elapsed_s=elapsed,
            engine={
                "backend": engine.backend,
                "table_dtype": engine.table_dtype,
                "kernel": kernel_version(engine.table_dtype),
                "spmd": engine.spmd,
                "noc_config": engine.noc_config,
                "devices": 1 if mesh is None else int(mesh.size),
            },
        )
    finally:
        src.close()
