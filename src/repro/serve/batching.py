"""Shape-bucketed micro-batching for the CAM serving path.

Production tabular traffic arrives as many small, ragged query batches
(typically a single row per request).  Feeding those shapes straight into
``XTimeEngine`` would trigger one ``jax.jit`` compilation per distinct
request size and pay a full dispatch per request.  Instead the batcher:

  1. coalesces pending requests (arrival order) into one query block,
  2. pads the block to the smallest admissible BUCKET — powers of two up
     to ``b_blk``, then ``b_blk`` multiples up to ``max_batch`` — so the
     engine compiles once per bucket, ``O(log max_batch)`` programs total,
  3. runs the engine's donated ``padded_fn`` once per flush,
  4. un-pads and splits the outputs back to the individual requests in
     their original order.

Each step runs under an ``xtime.serve.<step>`` profiler span (coalesce,
pad, dispatch, wait, fetch; ``repro.obs``).

Batches larger than ``max_batch`` still get served: the fallback bucket is
the next ``b_blk`` multiple (an uncached compile — logged, not fatal),
mirroring how the chip handles over-capacity models by spilling to
multi-chip placement rather than rejecting them (DESIGN.md §6).
"""

from __future__ import annotations

import logging
import threading
from dataclasses import dataclass, field

import numpy as np

from repro.kernels import ops as kops
from repro.obs import span

log = logging.getLogger(__name__)


def _ceil_to(x: int, m: int) -> int:
    return int(np.ceil(x / m)) * m


@dataclass(frozen=True)
class BucketSpec:
    """The admissible padded batch sizes for one served model.

    ``multiple`` comes from ``XTimeEngine.batch_multiple``: 1 for the jnp
    oracle (power-of-two buckets allowed below ``b_blk``), ``b_blk`` for
    the Pallas kernel whose grid tiles the batch, and the mesh batch-shard
    count for distributed engines (which can exceed ``b_blk`` — e.g. 256
    shards on the 16x16 production mesh with the 'batch' NoC config).
    Large buckets step by ``lcm(b_blk, multiple)`` so every constraint
    holds simultaneously.
    """

    b_blk: int = 128
    max_batch: int = 1024
    multiple: int = 1

    def __post_init__(self) -> None:
        if self.multiple < 1 or self.b_blk < 1:
            raise ValueError("b_blk and multiple must be >= 1")
        if self.max_batch < self._step():
            raise ValueError(
                f"max_batch={self.max_batch} must be >= the smallest large "
                f"bucket lcm(b_blk={self.b_blk}, multiple={self.multiple})"
                f"={self._step()}"
            )

    def _step(self) -> int:
        return int(np.lcm(self.b_blk, self.multiple))

    def sizes(self) -> list[int]:
        """All cached bucket sizes, ascending: power-of-two multiples of
        ``multiple`` below the large-bucket step, then step multiples."""
        step = self._step()
        out = []
        p = self.multiple
        while p < step:
            out.append(p)
            p *= 2
        out.extend(range(step, self.max_batch + 1, step))
        return out

    def select(self, n: int) -> int:
        """Smallest bucket holding ``n`` rows (over-max falls back to the
        next step multiple — admissible but uncached)."""
        if n <= 0:
            raise ValueError("empty batch")
        for s in self.sizes():
            if n <= s:
                return s
        fallback = _ceil_to(n, self._step())
        log.warning(
            "batch of %d rows exceeds max_batch=%d; using uncached bucket %d",
            n, self.max_batch, fallback,
        )
        return fallback


@dataclass
class PendingRequest:
    """One enqueued query batch awaiting a flush."""

    request_id: int
    q_bins: np.ndarray  # (b, F) int
    t_enqueue: float = 0.0

    @property
    def n_rows(self) -> int:
        return int(self.q_bins.shape[0])


@dataclass
class MicroBatcher:
    """Coalesces requests for ONE engine into bucket-padded flushes.

    The batcher owns ordering: requests are concatenated in arrival order
    and results are handed back keyed by request id, so interleaving or
    re-submitting out of order cannot mis-route rows.

    Thread safety: ``submit``/``flush``/queue inspection may be called
    from concurrent threads (the async cluster tier drives one batcher
    from intake and worker threads at once).  The queue is mutated only
    under ``_lock``; a flush atomically takes the whole pending list and
    runs the engine OUTSIDE the lock, so submits keep landing while a
    flush computes and two racing flushes serve disjoint batches.
    """

    # XTimeEngine (duck-typed: padded_fn/arrays/batch_multiple/select_features)
    engine: "object"
    bucket: BucketSpec = field(default_factory=BucketSpec)
    kind: str = "predict"
    _pending: list[PendingRequest] = field(default_factory=list)
    _next_id: int = 0
    _lock: threading.RLock = field(
        default_factory=threading.RLock, repr=False, compare=False
    )

    @classmethod
    def for_engine(cls, engine, *, max_batch: int = 1024, kind: str = "predict"):
        return cls(
            engine=engine,
            bucket=BucketSpec(
                b_blk=engine.b_blk,
                max_batch=max_batch,
                multiple=engine.batch_multiple,
            ),
            kind=kind,
        )

    # -- queue ---------------------------------------------------------------

    def submit(
        self,
        q_bins: np.ndarray,
        *,
        t_enqueue: float = 0.0,
        request_id: int | None = None,
    ) -> int:
        """Enqueue one request batch; returns its request id.

        ``request_id`` lets an owner (ServeLoop) allocate ids globally so
        handles stay unique across batcher replacements (hot swap).
        """
        # copy: the queue may hold this until a much later flush, and the
        # caller is free to reuse/overwrite its buffer after submit()
        q = np.array(q_bins)
        if q.ndim == 1:
            q = q[None, :]
        if q.ndim != 2 or q.shape[0] == 0:
            raise ValueError(f"expected (b, F) query rows, got shape {q.shape}")
        with self._lock:
            if request_id is None:
                request_id = self._next_id
                self._next_id += 1
            else:
                self._next_id = max(self._next_id, request_id + 1)
            self._pending.append(PendingRequest(request_id, q, t_enqueue))
        return request_id

    @property
    def pending_rows(self) -> int:
        with self._lock:
            return sum(p.n_rows for p in self._pending)

    @property
    def pending_requests(self) -> int:
        with self._lock:
            return len(self._pending)

    def oldest_enqueue_time(self) -> float | None:
        with self._lock:
            return self._pending[0].t_enqueue if self._pending else None

    def warm(self) -> None:
        """Compile (and run once) everything a flush of each cached
        bucket runs on the device, so no such flush waits on a compile."""
        width = self.engine.table.n_features
        for size in self.bucket.sizes():
            self._run(np.zeros((1, width), dtype=np.int32), size)

    def _run(self, q: np.ndarray, size: int):
        """Engine outputs, on the device, for the rows ``q`` padded to
        ``size``.

        Rows are padded on the host, in the table dtype, before anything
        reaches the device: every device op below (narrowing, feature
        padding, the engine program) is then keyed by the bucket alone.
        Padding on the device keyed its eager ops by the request count,
        and each new count compiled during a flush.
        """
        with span("xtime.serve.pad"):
            dtype = np.dtype(self.engine.table_dtype)
            kops.check_query_range(q, dtype)
            rows = np.zeros((size, q.shape[1]), dtype=dtype)
            rows[: q.shape[0]] = q
            # compressed tables dropped wildcard columns: narrow the
            # full-width request rows to the stored columns BEFORE padding
            # to f_pad — padding first would bake misaligned columns into
            # the bucket
            q_sel = self.engine.select_features(rows)
            q_padded = kops.pad_to_bucket(
                q_sel, size, self.engine.arrays.f_pad, dtype=dtype
            )
        with span("xtime.serve.dispatch"):
            out = self.engine.padded_fn(self.kind)(q_padded)
        with span("xtime.serve.wait"):
            return out.block_until_ready()

    # -- flush ---------------------------------------------------------------

    def serve(self, blocks: list[np.ndarray]) -> list[np.ndarray]:
        """Outputs for each block of query rows, in order, from one
        coalesced engine call over all of them."""
        with span("xtime.serve.coalesce"):
            q = np.concatenate(blocks, axis=0)
            size = self.bucket.select(q.shape[0])
        out = self._run(q, size)
        with span("xtime.serve.fetch"):
            out = np.asarray(out)
            parts, row = [], 0
            for b in blocks:
                parts.append(out[row : row + b.shape[0]])
                row += b.shape[0]
        return parts

    def flush(self) -> dict[int, np.ndarray]:
        """Run one coalesced engine call; returns {request_id: outputs}.

        Output rows per request exactly match what a direct
        ``engine.predict``/``raw_margin`` call on that request would give
        (the correctness contract tested in tests/test_serving.py).
        """
        with self._lock:
            if not self._pending:
                return {}
            batch, self._pending = self._pending, []
        outs = self.serve([p.q_bins for p in batch])
        return {p.request_id: out for p, out in zip(batch, outs)}
