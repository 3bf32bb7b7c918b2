"""X-TIME inference engine: compiled CAM table -> batched predictions.

Single-device path: the Pallas kernel (TPU) or its jnp oracle (CPU),
under a plain ``jax.jit``.  Execution knobs arrive as a ``DeployConfig``
(``XTimeEngine.from_config`` / ``CompiledModel.engine``); the loose-kwarg
constructor form is deprecated.

Kernel v2 (DESIGN.md §10): at bind time the engine packs the canonical
int32 exclusive-high table into the narrowest dtype the grid permits
(``resolve_table_dtype`` — uint8 for ≤256 bins, inclusive upper bounds,
compared natively), transposes the bounds feature-major ``(F_pad,
R_pad)`` (CAM rows on the kernel's lanes; the jnp reference transposes
them back), precomputes the wildcard group-activity mask the kernel
uses to skip all-wildcard feature groups, and resolves
``interpret='auto'`` against the bound platform.  All of it is
semantics-free: every (backend, mode, table_dtype) combination computes
identical bits (tests/test_kernel_v2.py).

Scale-out path (``config.spmd``, DESIGN.md §8): on a mesh the CAM rows
(cores) shard over ``config.row_axis`` and the query batch over
``config.batch_axis`` (× ``pod``), and the §III-D H-tree router program
becomes collectives in one of two partitioning modes:

  * ``spmd='shard_map'`` (default with a mesh) — the kernel runs once
    per device shard and the NoC plan is issued as EXPLICIT collectives:
    ``psum`` over the row axis for ``noc_config='accumulate'``, no
    collective for the replicated-table ``'batch'`` program, and
    all-gather + ``psum_scatter`` for the 2-D ``'hybrid'`` program.
  * ``spmd='gspmd'`` — implicit ``NamedSharding`` placement; the XLA
    partitioner places the equivalent collectives.  Kept as the
    independent oracle the shard_map path is property-tested
    bit-equivalent against (tests/test_scaleout.py).

The engine reproduces ``Ensemble.raw_margin`` / ``Ensemble.predict``
bit-for-bit on binned inputs — that equivalence is the correctness
contract (tested in tests/test_engine.py), and it holds across every
(spmd, noc_config) combination.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.compile import CAMTable
from repro.core.deploy import DeployConfig
from repro.core.precision import get_cell_mode
from repro.kernels import ops as kops
from repro.kernels.cam_match import default_interpret
from repro.kernels.ref import cam_match_ref

_UNSET = object()  # distinguishes "kwarg not passed" from an explicit default


def resolve_table_dtype(table: CAMTable, config: DeployConfig) -> str:
    """Effective kernel table dtype for this (table, config) binding.

    Modes with a pinned ``CellMode.table_dtype_policy`` always run that
    layout (int32 exclusive-high for the bit-faithful macro-cell modes,
    float32 soft-encoded bounds for 'soft' — ``DeployConfig`` rejects
    conflicting explicit dtypes); otherwise 'auto' takes the
    compile-time selection carried on the table, and an explicit packed
    dtype must actually hold the grid (inclusive bounds -> n_bins-1).
    """
    policy = get_cell_mode(config.mode).table_dtype_policy
    if policy is not None:
        return policy
    dt = table.table_dtype if config.table_dtype == "auto" else config.table_dtype
    if dt != "int32" and table.n_bins - 1 > np.iinfo(dt).max:
        raise ValueError(
            f"table_dtype {dt!r} cannot hold n_bins={table.n_bins} "
            "(inclusive bounds store values up to n_bins-1)"
        )
    return dt


@dataclass
class EngineArrays:
    low: jnp.ndarray  # (F_pad, R_pad) table dtype, feature-major
    high: jnp.ndarray  # (inclusive upper bounds when packed)
    leaf: jnp.ndarray  # (R_pad, C_pad) float32
    tile_mask: jnp.ndarray  # (R_pad/r_blk, n_groups) int32
    r_pad: int
    f_pad: int
    c_pad: int
    n_feat: int  # the table's real width; the kernel compares no more
    table_dtype: str = "int32"
    inclusive: bool = False  # high bounds stored inclusive?


class XTimeEngine:
    """Batched tree-ensemble inference on a compiled CAM table.

    Args:
      table: compiled ensemble.
      config: a ``DeployConfig`` holding every execution knob — the
        canonical construction path (``XTimeEngine.from_config`` /
        ``CompiledModel.engine``).  'auto' noc_config resolves to
        'accumulate' here; the artifact layer resolves it from the
        compiled NoC plan before binding.
      mesh: optional jax Mesh. When given, rows are sharded over
        ``config.row_axis`` and batch over ``config.batch_axis`` (+
        leading 'pod' axis if present), and ``config.noc_config`` picks
        the collective program realizing the paper's router bits
        ('accumulate' / 'batch' / 'hybrid').  ``config.spmd`` selects
        explicit shard_map collectives (default on a mesh) or implicit
        GSPMD partitioning — bit-equivalent paths, DESIGN.md §8.

    The loose keyword form (``backend=``, ``mode=``, ``b_blk=``, ...) is
    deprecated: those knobs now live in ``DeployConfig``.  It still works
    — the kwargs are folded into a config — but emits a
    ``DeprecationWarning``.
    """

    def __init__(
        self,
        table: CAMTable,
        *,
        config: DeployConfig | None = None,
        mesh: Mesh | None = None,
        place: bool = True,
        backend=_UNSET,
        mode=_UNSET,
        row_axis=_UNSET,
        batch_axis=_UNSET,
        noc_config=_UNSET,
        b_blk=_UNSET,
        r_blk=_UNSET,
        c_mult=_UNSET,
        interpret=_UNSET,
    ) -> None:
        legacy = {
            k: v
            for k, v in (
                ("backend", backend), ("mode", mode), ("row_axis", row_axis),
                ("batch_axis", batch_axis), ("noc_config", noc_config),
                ("b_blk", b_blk), ("r_blk", r_blk), ("c_mult", c_mult),
                ("interpret", interpret),
            )
            if v is not _UNSET
        }
        if legacy:
            if config is not None:
                raise TypeError(
                    "pass execution knobs via config=DeployConfig(...) OR as "
                    f"loose kwargs, not both (got config and {sorted(legacy)})"
                )
            warnings.warn(
                "loose XTimeEngine execution kwargs are deprecated; pass "
                "config=DeployConfig(...) or use repro.api.build(...).engine()",
                DeprecationWarning,
                stacklevel=2,
            )
            config = DeployConfig(**legacy)
        config = config or DeployConfig()

        self.table = table
        self.config = config
        # compressed tables may have dropped all-wildcard feature columns
        # (repro.core.compress): queries arrive at the LOGICAL width and
        # are narrowed to the stored columns before any padding/matching
        self.feature_ids = (
            None
            if table.feature_ids is None
            else np.asarray(table.feature_ids, dtype=np.int64)
        )
        # column-clustered tables (order_columns_by_activity) additionally
        # permute their stored columns; queries follow AFTER the narrowing
        self.col_perm = (
            None
            if table.col_perm is None
            else np.asarray(table.col_perm, dtype=np.int64)
        )
        self.backend = config.backend
        self.mode = config.mode
        self.mesh = mesh
        self.row_axis = config.row_axis
        self.batch_axis = config.batch_axis
        noc_cfg = config.noc_config
        self.noc_config = "accumulate" if noc_cfg == "auto" else noc_cfg
        self.b_blk = config.b_blk
        self.r_blk = config.r_blk
        self.f_blk = config.f_blk
        # 'auto' interpret resolves against the bound platform: compiled
        # Pallas on TPU, the interpreter everywhere else — so callers never
        # hard-code the slow interpreter onto real hardware again
        self.interpret = (
            default_interpret() if config.interpret == "auto"
            else bool(config.interpret)
        )
        # kernel v2 compact layout: the narrowest dtype the grid permits
        # (DESIGN.md §10).  Packed tables store inclusive upper bounds and
        # compare with the 'inclusive' cell, bit-equal to 'direct' on the
        # exclusive layout; the faithful modes stay on int32.
        self.table_dtype = resolve_table_dtype(table, config)
        if get_cell_mode(config.mode).soft:
            self.kernel_mode = "soft"
        elif np.dtype(self.table_dtype).kind == "u":
            self.kernel_mode = "inclusive"
        else:
            self.kernel_mode = config.mode
        # soft-mode boundary temperature — static (selects the trace);
        # pinned to 0.0 for hard modes so they share one jit cache entry
        # regardless of the config's tau knob
        self.tau = float(config.tau) if self.kernel_mode == "soft" else 0.0
        # kernel v3 fused epilogue: the base-score add rides the kernel's
        # last row tile.  Only the single-device pallas path is
        # eligible — under a row-sharded mesh the per-shard partials are
        # psum'd, which would count the base once per shard.
        eligible = self.backend == "pallas" and mesh is None
        if config.fuse_epilogue == "auto":
            self.fuse_epilogue = eligible
        else:
            self.fuse_epilogue = bool(config.fuse_epilogue)
            if self.fuse_epilogue and not eligible:
                raise ValueError(
                    "fuse_epilogue=True needs backend='pallas' and no mesh "
                    "(a row-sharded reduction would multiply the base "
                    "score); use 'auto' to fuse only when eligible"
                )
        # 'auto' partitioning resolves at bind time: explicit shard_map
        # collectives when there is a mesh to communicate over, plain jit
        # otherwise (without a mesh both modes are the same program).
        if mesh is None:
            self.spmd = "gspmd"
        elif config.spmd == "auto":
            self.spmd = "shard_map"
        else:
            self.spmd = config.spmd
        if mesh is not None:
            missing = [
                ax
                for ax in (self.row_axis, self.batch_axis)
                if ax not in mesh.axis_names
            ]
            if missing:
                raise ValueError(
                    f"mesh {mesh.axis_names} lacks configured axes {missing}"
                )
            if self.noc_config == "hybrid" and self.spmd != "shard_map":
                raise ValueError(
                    "noc_config='hybrid' (all-gather + psum_scatter) is only "
                    "expressible with spmd='shard_map'"
                )

        # row padding must also be divisible by the row-shard count
        row_mult = self.r_blk
        if mesh is not None and self.noc_config in ("accumulate", "hybrid"):
            row_mult = self.r_blk * mesh.shape[self.row_axis]
        # feature-major bounds (F_pad, R_pad), transposed here once
        low, high, leaf, inclusive = kops.pack_tables(
            table.low, table.high, table.leaf_matrix(),
            r_blk=row_mult, c_mult=config.c_mult, n_bins=table.n_bins,
            dtype=self.table_dtype,
            inclusive=(True if self.kernel_mode == "inclusive" else None),
        )
        n_feat = max(1, table.low.shape[1])
        tile_mask = kops.wildcard_tile_mask(
            low, high, r_blk=self.r_blk, f_blk=self.f_blk,
            n_bins=table.n_bins, inclusive=inclusive, n_feat=n_feat,
        )
        # the share of (row tile, feature group) compares the kernel runs:
        # 1.0 means wildcard skipping never engages on this table
        self.mask_active_share = float(tile_mask.mean())
        self.arrays = EngineArrays(
            low=jnp.asarray(low),
            high=jnp.asarray(high),
            leaf=jnp.asarray(leaf),
            tile_mask=jnp.asarray(tile_mask),
            r_pad=low.shape[1],
            f_pad=low.shape[0],
            c_pad=leaf.shape[1],
            n_feat=n_feat,
            table_dtype=self.table_dtype,
            inclusive=inclusive,
        )
        # fused-epilogue bias row: base score broadcast over C_pad (the
        # padding channels are sliced off by the epilogue, so the extra
        # adds are dead); None when the epilogue stays separate
        self._bias = (
            jnp.full((1, self.arrays.c_pad), jnp.float32(table.base_score))
            if self.fuse_epilogue
            else None
        )
        # soft mode's uncertainty channel (DESIGN.md §15): a SEPARATE
        # moments leaf matrix [leaf, leaf^2, mass] scattered per output
        # channel.  One extra kernel pass over it yields the raw weighted
        # sums (m1, m2, mass) the leaf-spread uncertainty derives from —
        # keeping the margin/predict path on the plain leaf matrix, whose
        # operand shapes (and therefore float reduction order, and the
        # tau->0 bit-equality with 'direct') stay identical to the hard
        # modes.  Bias is never fused into this pass.
        self._moments = None
        if self.kernel_mode == "soft":
            lm = np.asarray(table.leaf_matrix(), dtype=np.float32)  # (R, C)
            R, C = lm.shape
            onehot = np.zeros_like(lm)
            cls = np.asarray(table.class_id, dtype=np.int64) % max(1, C)
            onehot[np.arange(R), cls] = 1.0  # row mass per output channel
            m = np.concatenate([lm, lm * lm, onehot], axis=1)  # (R, 3C)
            c3_pad = -(-3 * C // config.c_mult) * config.c_mult
            m_pad = np.zeros((self.arrays.r_pad, c3_pad), dtype=np.float32)
            m_pad[:R, : 3 * C] = m
            self._moments = jnp.asarray(m_pad)
        if mesh is not None and place:
            self._place_on_mesh()
        self._fn_cache: dict = {}

    @classmethod
    def from_config(
        cls, table: CAMTable, config: DeployConfig, *,
        mesh: Mesh | None = None, place: bool = True,
    ) -> "XTimeEngine":
        """Canonical constructor: bind a compiled table + deploy config to a
        backend/mesh.  ``config.noc_config`` must already be resolved
        ('auto' is treated as 'accumulate'); ``CompiledModel.engine``
        resolves it from the NoC plan first.  ``place=False`` makes every
        mesh-dependent decision but leaves the arrays unplaced — for
        lowering against a described topology, whose devices hold no
        arrays."""
        return cls(table, config=config, mesh=mesh, place=place)

    # -- placement ---------------------------------------------------------

    def _batch_spec(self) -> P:
        axes = [self.batch_axis]
        if self.mesh is not None and "pod" in self.mesh.axis_names:
            axes = ["pod", self.batch_axis]
        if self.noc_config in ("batch", "hybrid"):
            axes.append(self.row_axis)  # batch over cores too
        return P(tuple(axes))

    def _row_spec(self) -> P:
        """Row sharding of the leaf matrix and the tile mask (rows first)."""
        if self.noc_config == "batch":
            return P()  # table replicated in every core group
        return P(self.row_axis)

    def _bound_spec(self) -> P:
        """Row sharding of the feature-major bounds: rows are axis 1."""
        if self.noc_config == "batch":
            return P()
        return P(None, self.row_axis)

    def _table_specs(self) -> tuple[P, P, P, P]:
        """Specs of (low, high, leaf, tile_mask)."""
        rs, bs = self._row_spec(), self._bound_spec()
        return bs, bs, rs, rs

    def _place_on_mesh(self) -> None:
        assert self.mesh is not None
        a = self.arrays
        lo_s, hi_s, leaf_s, mask_s = (
            NamedSharding(self.mesh, s) for s in self._table_specs()
        )
        a.low = jax.device_put(a.low, lo_s)
        a.high = jax.device_put(a.high, hi_s)
        a.leaf = jax.device_put(a.leaf, leaf_s)
        # the tile-activity mask shards with the rows it describes
        a.tile_mask = jax.device_put(a.tile_mask, mask_s)
        if self._moments is not None:  # soft moments shard like the leaves
            self._moments = jax.device_put(self._moments, leaf_s)

    # -- compute -----------------------------------------------------------

    def _kernel_fn(self, bias=_UNSET) -> Callable:
        """(q, low, high, leaf, mask) -> (B, C_pad) raw accumulated leaf
        sums over the rows it is handed — no epilogue, no collectives.
        Under shard_map the operands (and B/R) are per-shard.  ``bias``
        defaults to the engine's fused-epilogue row; the moments path
        passes None (no base score belongs in the raw moment sums)."""
        backend, mode, tau = self.backend, self.kernel_mode, self.tau
        b_blk, r_blk, f_blk = self.b_blk, self.r_blk, self.f_blk
        interpret, n_feat = self.interpret, self.arrays.n_feat
        if bias is _UNSET:
            bias = self._bias

        def kernel(q, low, high, leaf, mask):
            if backend == "pallas":
                return kops.cam_match(
                    q, low, high, leaf, mask, bias,
                    out_b=q.shape[0], out_c=leaf.shape[1],
                    b_blk=b_blk, r_blk=r_blk, f_blk=f_blk,
                    mode=mode, interpret=interpret, tau=tau, n_feat=n_feat,
                )
            # the reference compares row-major (R, F) tables
            return cam_match_ref(q, low.T, high.T, leaf, mode=mode, tau=tau)

        return kernel

    def _epilogue_fn(self) -> Callable:
        """Channel slice + base score + RF averaging — applied exactly once,
        AFTER any cross-core reduction (adding the base score per shard
        would count it row-shard-count times).  When the engine fuses the
        epilogue into the kernel (kernel v3) the base score already landed
        on each output tile's last visit — in the same float order, so the
        bits match — and only the slice (+ RF divide) remains here."""
        table, fused = self.table, self.fuse_epilogue

        def epilogue(out):
            out = out[:, : table.n_outputs]
            if not fused:
                out = out + jnp.float32(table.base_score)
            if table.kind == "rf":
                out = out / jnp.float32(max(1, table.n_trees))
            return out

        return epilogue

    def _margin_fn(self) -> Callable:
        """Raw-margin function of (q, low, high, leaf) — jit-compatible.

        With ``spmd='shard_map'`` the kernel runs per device shard and the
        NoC router program is issued as explicit collectives (DESIGN.md
        §8): ``accumulate`` -> psum of the partial margins over the row
        axis (the H-tree in-network reduction); ``batch`` -> replicated
        tables, batch split over every axis, no collective; ``hybrid`` ->
        the queries arrive sharded over (batch × core), are all-gathered
        along the row axis, and the partial margins reduce-scatter back so
        the output stays 2-D-sharded (all-reduce cost without the
        replicated output of 'accumulate').
        """
        kernel, epilogue = self._kernel_fn(), self._epilogue_fn()
        reduced = self._reduced_fn(kernel)
        return lambda q, low, high, leaf, mask: epilogue(
            reduced(q, low, high, leaf, mask)
        )

    def _reduced_fn(self, kernel: Callable) -> Callable:
        """Wrap ``kernel`` with the cross-core reduction program: under
        ``spmd='shard_map'`` the NoC plan's explicit collectives, plain
        pass-through otherwise.  Shared by the margin and moments paths —
        both are row sums, so the same router program applies."""
        if self.mesh is not None and self.spmd == "shard_map":
            noc, row_axis = self.noc_config, self.row_axis

            def body(q, low, high, leaf, mask):
                if noc == "hybrid":
                    q = jax.lax.all_gather(q, row_axis, axis=0, tiled=True)
                out = kernel(q, low, high, leaf, mask)
                if noc == "accumulate":
                    out = jax.lax.psum(out, row_axis)
                elif noc == "hybrid":
                    out = jax.lax.psum_scatter(
                        out, row_axis, scatter_dimension=0, tiled=True
                    )
                return out

            qs = self._batch_spec()
            # replication checking off: the Pallas kernel body is opaque
            # to the varying-manual-axes checker
            return jax.shard_map(
                body, mesh=self.mesh, in_specs=(qs, *self._table_specs()),
                out_specs=qs, check_vma=False,
            )
        return kernel

    def _jitted(self, key: str, donate: bool = False) -> Callable:
        cache_key = (key, donate)
        if cache_key in self._fn_cache:
            return self._fn_cache[cache_key]
        table = self.table
        if key == "moments":
            # soft uncertainty channel: the same reduced kernel run over
            # the (R_pad, 3C) moments matrix instead of the leaves, with
            # no bias (a base score has no place in raw moment sums) and
            # an epilogue that only strips the channel padding
            reduced = self._reduced_fn(self._kernel_fn(bias=None))
            n3 = 3 * table.n_outputs

            def fn(q, low, high, leaf, mask):
                return reduced(q, low, high, leaf, mask)[:, :n3]

        else:
            margin = self._margin_fn()
            want_pred = key == "predict"

            def fn(q, low, high, leaf, mask):
                m = margin(q, low, high, leaf, mask)
                if not want_pred:
                    return m
                if table.task == "regression":
                    return m[:, 0]
                if table.n_outputs == 1:  # single-logit binary: sign test
                    return (m[:, 0] > 0.0).astype(jnp.int32)
                return jnp.argmax(m, axis=1).astype(jnp.int32)

        # The serving path donates the query buffer: each coalesced batch is
        # a freshly padded array that is dead after the call, so XLA may
        # reuse its storage (free on backends without donation support).
        donate_kw = {"donate_argnums": (0,)} if donate else {}
        if self.mesh is not None:
            bs = NamedSharding(self.mesh, self._batch_spec())
            ts = tuple(NamedSharding(self.mesh, s) for s in self._table_specs())
            jfn = jax.jit(fn, in_shardings=(bs, *ts), out_shardings=bs,
                          **donate_kw)
        else:
            jfn = jax.jit(fn, **donate_kw)
        self._fn_cache[cache_key] = jfn
        return jfn

    def select_features(self, q: np.ndarray | jnp.ndarray) -> jnp.ndarray:
        """Narrow ``(B, n_features)`` query bins to the stored table
        columns, then apply the compile-time column permutation
        (``CAMTable.col_perm``) — identity for plain tables.  Queries
        already at the (narrower) physical width pass through, so the
        serving batcher can narrow once per flush before bucket padding;
        a PURE permutation preserves the width, so that shortcut never
        applies to it and callers must pass logical-order queries (both
        serving paths — ``_prep_queries`` and the batcher flush — call
        this exactly once)."""
        q = jnp.asarray(q)
        fids, perm = self.feature_ids, self.col_perm
        if fids is None and perm is None:
            return q
        if (
            fids is not None
            and q.ndim == 2
            and q.shape[1] == fids.shape[0]
            and fids.shape[0] != self.table.n_features
        ):
            return q  # already narrowed (and permuted) by an earlier call
        if q.ndim != 2 or q.shape[1] != self.table.n_features:
            expect = f"expected (_, {self.table.n_features}) query bins"
            if fids is not None:
                expect += f" (or pre-selected (_, {fids.shape[0]}))"
            raise ValueError(f"{expect}, got {q.shape}")
        if fids is not None:
            q = q[:, fids]
        if perm is not None:
            q = q[:, perm]
        return q

    def _prep_queries(self, q_bins: np.ndarray | jnp.ndarray) -> jnp.ndarray:
        # pad to a batch both the kernel tiling and the mesh sharding accept
        mult = int(np.lcm(self.b_blk, self.batch_multiple))
        q = kops.pad_queries(
            self.select_features(q_bins), self.arrays.f_pad, b_blk=mult,
            dtype=self.table_dtype,
        )
        if self.mesh is not None:
            q = jax.device_put(q, NamedSharding(self.mesh, self._batch_spec()))
        return q

    def raw_margin(self, q_bins: np.ndarray | jnp.ndarray) -> jnp.ndarray:
        """(B, n_outputs) — matches ``Ensemble.raw_margin`` on binned input."""
        B = q_bins.shape[0]
        q = self._prep_queries(q_bins)
        a = self.arrays
        return self._jitted("margin")(q, a.low, a.high, a.leaf, a.tile_mask)[:B]

    def predict(self, q_bins: np.ndarray | jnp.ndarray) -> jnp.ndarray:
        """Final predictions — matches ``Ensemble.predict``."""
        B = q_bins.shape[0]
        q = self._prep_queries(q_bins)
        a = self.arrays
        return self._jitted("predict")(q, a.low, a.high, a.leaf, a.tile_mask)[:B]

    # -- soft-mode uncertainty channel (DESIGN.md §15) -----------------------

    def raw_moments(self, q_bins: np.ndarray | jnp.ndarray) -> jnp.ndarray:
        """(B, 3*n_outputs) raw soft moments ``[m1 | m2 | mass]``.

        Per output channel c: ``m1 = sum_r s_r * leaf[r, c]``,
        ``m2 = sum_r s_r * leaf[r, c]^2`` and ``mass = sum_r s_r`` over
        the rows routed to c, with s_r the row's soft match score — the
        weighted leaf-value moments the spread/uncertainty derives from.
        Soft engines only."""
        if self._moments is None:
            raise ValueError(
                "raw_moments/uncertainty require the soft cell mode "
                f"(this engine runs mode={self.mode!r}); rebind with "
                "DeployConfig(mode='soft')"
            )
        B = q_bins.shape[0]
        q = self._prep_queries(q_bins)
        a = self.arrays
        out = self._jitted("moments")(
            q, a.low, a.high, self._moments, a.tile_mask
        )
        return out[:B]

    def uncertainty(self, q_bins: np.ndarray | jnp.ndarray) -> jnp.ndarray:
        """(B, n_outputs) calibrated uncertainty: the score-weighted
        population spread (std) of the leaf values behind each output
        channel.  At tau=0 exactly one row per tree matches, every
        weight is 0/1 and the spread is the honest across-tree
        disagreement; finite tau additionally counts boundary ambiguity
        (several leaves of one tree sharing a query's weight)."""
        m = np.asarray(self.raw_moments(q_bins), dtype=np.float64)
        C = self.table.n_outputs
        m1, m2, mass = m[:, :C], m[:, C : 2 * C], m[:, 2 * C : 3 * C]
        mass = np.maximum(mass, 1e-12)  # empty channels -> 0 spread, not NaN
        mean = m1 / mass
        var = np.maximum(m2 / mass - mean * mean, 0.0)
        return jnp.asarray(np.sqrt(var, dtype=np.float64).astype(np.float32))

    # -- bucketed serving path ----------------------------------------------

    @property
    def batch_multiple(self) -> int:
        """Smallest batch granularity a serving bucket must respect.

        The Pallas kernel tiles the batch in ``b_blk`` blocks, so its
        buckets must be ``b_blk`` multiples; the jnp/XLA oracle accepts any
        batch, letting the serving layer use power-of-two buckets below
        ``b_blk``.  A mesh additionally requires the batch axis to divide
        evenly across its batch shards — and under ``spmd='shard_map'``
        each shard's LOCAL batch runs the Pallas kernel on its own, so
        the global batch must be a ``b_blk × shards`` multiple.
        """
        mult = self.b_blk if self.backend == "pallas" else 1
        if self.mesh is not None:
            shards = self.mesh.shape[self.batch_axis]
            if "pod" in self.mesh.axis_names:
                shards *= self.mesh.shape["pod"]
            if self.noc_config in ("batch", "hybrid"):
                shards *= self.mesh.shape[self.row_axis]
            if self.spmd == "shard_map" and self.backend == "pallas":
                mult = self.b_blk * shards
            else:
                mult = max(mult, shards)
        return mult

    def padded_fn(self, kind: str = "predict") -> Callable:
        """Bucket-aware jitted entry for the serving layer.

        Returns a callable of one pre-padded ``(bucket_b, f_pad)`` int32
        query block (see ``kops.pad_to_bucket``) that yields the FULL
        padded output — the caller owns un-padding.  ``jax.jit``
        specializes once per bucket shape, so a shape-bucketed request
        stream compiles ``O(log max_batch)`` variants instead of one per
        request size.  The query buffer is donated (dead after the call).
        """
        if kind not in ("predict", "margin"):
            raise ValueError(f"unknown kind {kind!r}")
        jfn = self._jitted(kind, donate=True)
        a = self.arrays

        def run(q_padded: jnp.ndarray) -> jnp.ndarray:
            if q_padded.ndim != 2 or q_padded.shape[1] != a.f_pad:
                raise ValueError(
                    f"expected (_, {a.f_pad}) padded queries, got {q_padded.shape}"
                )
            if q_padded.dtype != np.dtype(self.table_dtype):
                # packed engines compare queries in the table dtype; casting
                # here keeps pre-v2 callers (int32 buckets) on one jit entry
                # (wrap-checked: a narrowed out-of-range bin would match
                # rows it must not)
                kops.check_query_range(q_padded, self.table_dtype)
                q_padded = q_padded.astype(np.dtype(self.table_dtype))
            if q_padded.shape[0] % self.batch_multiple:
                raise ValueError(
                    f"bucket {q_padded.shape[0]} not a multiple of "
                    f"batch_multiple={self.batch_multiple}"
                )
            if self.mesh is not None:
                q_padded = jax.device_put(
                    q_padded, NamedSharding(self.mesh, self._batch_spec())
                )
            with warnings.catch_warnings():
                # integer queries can never alias the float32 outputs (and
                # CPU lacks donation entirely); donation still releases the
                # buffer early on TPU, so keep it but drop the noise.
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable"
                )
                return jfn(q_padded, a.low, a.high, a.leaf, a.tile_mask)

        return run

    def predict_padded(self, q_padded: jnp.ndarray) -> jnp.ndarray:
        """``predict`` on a pre-padded bucket; returns padded outputs."""
        return self.padded_fn("predict")(q_padded)

    def raw_margin_padded(self, q_padded: jnp.ndarray) -> jnp.ndarray:
        """``raw_margin`` on a pre-padded bucket; returns padded outputs."""
        return self.padded_fn("margin")(q_padded)

    # -- dry-run hooks -------------------------------------------------------

    def serve_step_for_dryrun(self):
        """(fn, in_shardings, out_shardings) for launch/dryrun.py."""
        assert self.mesh is not None, "dry-run requires a mesh"
        margin = self._margin_fn()
        bs = NamedSharding(self.mesh, self._batch_spec())
        ts = tuple(NamedSharding(self.mesh, s) for s in self._table_specs())
        return margin, (bs, *ts), bs

    def compiled_text(self, kind: str = "margin") -> str:
        """HLO text of the compiled ``kind`` program at its smallest
        admissible batch — how a caller sees what the device runs (the
        compiled Pallas kernel appears as ``tpu_custom_call``, the NoC
        collectives as ``all-reduce`` & co.)."""
        a = self.arrays
        q = self.input_specs(int(np.lcm(self.b_blk, self.batch_multiple)))
        lowered = self._jitted(kind).lower(q, a.low, a.high, a.leaf, a.tile_mask)
        return lowered.compile().as_text()

    def input_specs(self, batch: int) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(
            (batch, self.arrays.f_pad), np.dtype(self.table_dtype)
        )
