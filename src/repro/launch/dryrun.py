import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (including repro.*):
# jax locks the device count at first initialization, and the multi-pod
# dry-run needs 512 placeholder host devices to build the production mesh.
# Do NOT move them or set this flag globally — smoke tests and benchmarks
# must see the real single device.

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.config import SHAPES, get_config  # noqa: E402
from repro.launch import hlo_analysis  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.model_flops import model_flops  # noqa: E402
from repro.models.registry import build_model  # noqa: E402
from repro.optim.adamw import AdamW, AdamWConfig  # noqa: E402
from repro.sharding.partition import (  # noqa: E402
    MeshAxes,
    activation_sharder,
    attach,
    batch_pspec,
    cache_pspecs,
    param_pspecs,
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces (per the assignment):
  * compiled.memory_analysis()  — proves the program fits (bytes/device),
  * compiled.cost_analysis()    — raw XLA numbers (scan bodies counted
    once; kept for reference),
  * hlo_analysis.analyze()      — trip-count-aware dot FLOPs, fusion-
    boundary HBM bytes and collective bytes (the roofline inputs),
  * the three roofline terms + dominant bottleneck + MODEL_FLOPS ratio.

Results land in results/dryrun/<arch>__<shape>__<mesh>.json and are
aggregated into EXPERIMENTS.md by benchmarks/aggregate.py.
"""


def _moe_moment_dtype(cfg) -> str:
    # 671B-class models need bf16 moments to fit (DESIGN.md §5)
    return "bfloat16" if getattr(cfg, "n_experts", 0) >= 128 else "float32"


def lower_cell(arch: str, shape: str, multi_pod: bool, flash_blk: int = 1024):
    """Returns (lowered, meta) for one dry-run cell."""
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = MeshAxes(mesh)

    if getattr(cfg, "family", "") == "xtime":
        return _lower_xtime(cfg, shape, mesh, axes)

    cell = SHAPES[shape]
    if cell.name == "long_500k" and not cfg.supports_long_context:
        raise SkipCell(f"{arch} is pure full-attention; long_500k skipped per "
                       "assignment rule (see DESIGN.md §Arch-applicability)")

    bundle = build_model(cfg, flash_blk=flash_blk)
    bundle.model.shard_x = activation_sharder(mesh, axes)
    _install_moe_hooks(cfg, mesh, axes)
    params_sds = bundle.params_shape()
    pspecs = param_pspecs(params_sds, cfg, axes)
    params_in = attach(mesh, params_sds, pspecs)
    bspec = batch_pspec(axes)

    def shard_batch(tree):
        def one(sds):
            if len(sds.shape) >= 1 and sds.shape[0] == cell.global_batch:
                spec = axes.fit(
                    tuple(bspec) + (None,) * (len(sds.shape) - 1), sds.shape
                )
            else:
                spec = P()
            return jax.ShapeDtypeStruct(
                sds.shape, sds.dtype, sharding=NamedSharding(mesh, spec)
            )

        return jax.tree.map(one, tree)

    specs = bundle.input_specs(cell)

    if cell.kind == "train":
        opt = AdamW(AdamWConfig(moment_dtype=_moe_moment_dtype(cfg)))
        opt_sds = jax.eval_shape(opt.init, params_sds)
        opt_specs = {
            "m": pspecs, "v": pspecs,
            "step": P(),
        }
        opt_in = attach(mesh, opt_sds, opt_specs)
        batch_in = shard_batch(specs)

        def train_step(params, opt_state, batch):
            (loss, metrics), grads = jax.value_and_grad(
                bundle.loss_fn, has_aux=True
            )(params, batch)
            new_params, new_opt, om = opt.update(grads, opt_state, params)
            return new_params, new_opt, {"loss": loss, **om}

        lowered = jax.jit(train_step).lower(params_in, opt_in, batch_in)
        fn_kind = "train_step"
    elif cell.kind == "prefill":
        batch_in = shard_batch(specs)

        def prefill_step(params, batch):
            logits, cache = bundle.prefill(params, batch)
            return logits, cache

        lowered = jax.jit(prefill_step).lower(params_in, batch_in)
        fn_kind = "serve_prefill"
    else:  # decode
        cache_sds = specs["cache"]
        cspecs = cache_pspecs(cache_sds, cfg, axes)
        cache_in = attach(mesh, cache_sds, cspecs)
        token_in = jax.ShapeDtypeStruct(
            specs["token"].shape, specs["token"].dtype,
            sharding=NamedSharding(
                mesh, axes.fit(tuple(bspec), specs["token"].shape)
            ),
        )
        pos_in = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))

        def serve_step(params, cache, token, pos):
            return bundle.decode_step(params, cache, token, pos)

        lowered = jax.jit(serve_step).lower(params_in, cache_in, token_in, pos_in)
        fn_kind = "serve_step"

    mf = model_flops(cfg, cell, bundle)
    meta = {
        "arch": arch, "shape": shape, "kind": fn_kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(np.prod(mesh.devices.shape)),
        "model_flops_total": mf,
    }
    return lowered, meta


class SkipCell(Exception):
    pass


def _install_moe_hooks(cfg, mesh, axes: MeshAxes) -> None:
    """Token-dim / expert-dim sharding constraints for the MoE dispatch.

    REPRO_MOE_IMPL=shardmap selects the explicit all-to-all shard_map
    implementation (§Perf D2) instead of the pjit path."""
    from repro.models import moe as moe_mod

    if not getattr(cfg, "n_experts", 0):
        moe_mod.set_shard_hooks(None, None)
        moe_mod.set_impl(None)
        return
    if os.environ.get("REPRO_MOE_IMPL", "") == "shardmap":
        from repro.models.moe_shardmap import make_shardmap_moe

        moe_mod.set_impl(make_shardmap_moe(mesh))
    else:
        moe_mod.set_impl(None)
    b = axes.batch_axes()
    bspec = b if len(b) > 1 else (b[0] if b else None)

    def shard_tokens(x):
        spec = axes.fit((bspec,) + (None,) * (x.ndim - 1), x.shape)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    def shard_experts(x):  # (E, C, d): EP on experts, DP on capacity slots
        spec = axes.fit(("model", axes.fsdp) + (None,) * (x.ndim - 2), x.shape)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    def shard_weights(w):  # (E, d, f): EP kept, fsdp axis gathered pre-use
        spec = axes.fit(("model", None, None), w.shape)
        return jax.lax.with_sharding_constraint(w, NamedSharding(mesh, spec))

    moe_mod.set_shard_hooks(shard_tokens, shard_experts, shard_weights)


# ---------------------------------------------------------------------------
# X-TIME tabular cell (the paper's own workload on the production mesh)
# ---------------------------------------------------------------------------


def _lower_xtime(cfg, shape: str, mesh, axes: MeshAxes, compact: bool = True):
    """CAM rows sharded on `model`, batch on `data`(x`pod`); the psum over
    `model` *is* the H-tree reduction (DESIGN.md §2).

    ``compact`` (§Perf X1, default after hillclimb): bounds stored as
    uint8 with INCLUSIVE upper bound (match = low <= q <= high; the
    paper's 8-bit grid fits exactly: never-match rows are low=1 > high=0,
    always-match cells low=0, high=255) and bf16 leaf values — a 4x cut
    of the dominant table-stream traffic vs the int32/f32 baseline.
    """
    from repro.kernels.ref import cam_match_ref

    batch = {"serve_32k": 32768, "serve_1m": 1_048_576}[shape]
    rows = cfg.n_trees * cfg.max_leaves  # 4096 x 256 = 1,048,576 CAM rows
    f_pad = int(np.ceil(cfg.n_features / 128)) * 128
    c_pad = 8
    bspec = batch_pspec(axes)
    rs = NamedSharding(mesh, P("model", None))
    bdt = jnp.uint8 if compact else jnp.int32
    q_in = jax.ShapeDtypeStruct((batch, f_pad), bdt,
                                sharding=NamedSharding(mesh, bspec))
    low_in = jax.ShapeDtypeStruct((rows, f_pad), bdt, sharding=rs)
    high_in = jax.ShapeDtypeStruct((rows, f_pad), bdt, sharding=rs)
    leaf_in = jax.ShapeDtypeStruct(
        (rows, c_pad), jnp.bfloat16 if compact else jnp.float32, sharding=rs
    )

    if compact:
        # row-chunked accumulation (§Perf X2): the kernel-style blocking.
        # A monolithic (B, R) match matrix materializes B*R bools many
        # times over (measured 2.8 s memory term / 1 TiB temps at R = 1M);
        # scanning row chunks and accumulating (B, C) logits keeps only a
        # (B, Rc) tile live per step — same numbers, ~30x less traffic.
        r_chunk = 65536

        chunk_rs = NamedSharding(mesh, P(None, "model", None))
        chunk_qs = NamedSharding(
            mesh, axes.fit((None,) + tuple(bspec) + (None,), (1, batch, 1))
        )
        b_chunk = min(batch, 131072)  # live (Bq, Rc) tile ≈ 8 GiB/dev

        def serve_step(q, low, high, leaf):
            nc = low.shape[0] // r_chunk
            nbq = q.shape[0] // b_chunk
            # keep row/batch dims sharded INSIDE each chunk — without the
            # constraints the reshapes replicate the operands and every
            # device scans all rows (measured: 16x compute).
            lows = jax.lax.with_sharding_constraint(
                low.reshape(nc, r_chunk, low.shape[1]), chunk_rs)
            highs = jax.lax.with_sharding_constraint(
                high.reshape(nc, r_chunk, high.shape[1]), chunk_rs)
            leafs = jax.lax.with_sharding_constraint(
                leaf.reshape(nc, r_chunk, leaf.shape[1]), chunk_rs)
            qs = jax.lax.with_sharding_constraint(
                q.reshape(nbq, b_chunk, q.shape[1]), chunk_qs)

            def q_step(_, qc):
                def step(acc, xs):
                    lo, hi, lf = xs
                    cell = (lo[None] <= qc[:, None, :]) & (qc[:, None, :] <= hi[None])
                    match = jnp.all(cell, axis=-1)  # (Bq, Rc)
                    return acc + jax.lax.dot(
                        match.astype(lf.dtype), lf,
                        preferred_element_type=jnp.float32,
                    ), None

                acc0 = jnp.zeros((qc.shape[0], leaf.shape[1]), jnp.float32)
                out, _ = jax.lax.scan(step, acc0, (lows, highs, leafs))
                return None, out

            _, outs = jax.lax.scan(q_step, None, qs)
            return outs.reshape(q.shape[0], leaf.shape[1])
    else:
        def serve_step(q, low, high, leaf):
            return cam_match_ref(q, low, high, leaf, mode="direct")

    lowered = jax.jit(serve_step).lower(q_in, low_in, high_in, leaf_in)
    # MODEL_FLOPS counts only MXU work (match @ leaf_matrix); the range
    # compares are VPU integer ops, reported separately so the useful-FLOP
    # ratio stays comparable with the LM rows.
    mf = 2.0 * float(batch) * rows * c_pad
    meta = {
        "arch": cfg.name, "shape": shape, "kind": "xtime_serve",
        "mesh": "2x16x16" if axes.pod else "16x16",
        "n_devices": int(np.prod(mesh.devices.shape)),
        "model_flops_total": mf,
        "compare_ops_total": 2.0 * float(batch) * rows * cfg.n_features,
    }
    return lowered, meta


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             flash_blk: int = 1024) -> dict:
    t0 = time.time()
    mesh_name = "multi" if multi_pod else "single"
    result: dict = {"arch": arch, "shape": shape, "mesh": mesh_name}
    try:
        lowered, meta = lower_cell(arch, shape, multi_pod, flash_blk)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        cost = hlo_analysis.analyze(compiled.as_text())
        n_dev = meta["n_devices"]
        terms = hlo_analysis.roofline_from_cost(
            cost, model_flops_per_dev=meta["model_flops_total"] / n_dev
        )
        result.update(meta)
        result.update({
            "status": "ok",
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "memory": {
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
                "code_bytes": int(mem.generated_code_size_in_bytes),
            },
            "cost_analysis_raw": {
                "flops": float(ca.get("flops", -1.0)),
                "bytes": float(ca.get("bytes accessed", -1.0)),
            },
            "hlo": {
                "dot_flops_per_dev": cost.dot_flops,
                "hbm_bytes_per_dev": cost.fusion_boundary_bytes,
                "collective_bytes_per_dev": cost.collective_bytes,
                "collective_breakdown": cost.collective_breakdown,
                "n_whiles": cost.n_whiles,
                "trip_counts": cost.trip_counts[:64],
            },
            "roofline": {
                "compute_s": terms.compute_s,
                "memory_s": terms.memory_s,
                "collective_s": terms.collective_s,
                "dominant": terms.dominant,
                "bound_s": terms.bound_s,
                "model_flops_ratio": terms.useful_flop_ratio,
            },
        })
        # per-device HBM check vs v5e (16 GiB)
        per_dev = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                   + mem.temp_size_in_bytes)
        result["memory"]["total_per_device_gib"] = round(per_dev / 2**30, 3)
        result["memory"]["fits_v5e_16gib"] = bool(per_dev < 16 * 2**30)
    except SkipCell as e:
        result.update({"status": "skip", "reason": str(e)})
    except Exception as e:  # noqa: BLE001
        result.update({
            "status": "error",
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        })
    result["wall_s"] = round(time.time() - t0, 1)
    os.makedirs(out_dir, exist_ok=True)
    fn = os.path.join(out_dir, f"{arch}__{shape}__{mesh_name}.json")
    with open(fn, "w") as f:
        json.dump(result, f, indent=1, default=float)
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description="X-TIME framework multi-pod dry-run")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out-dir", default="results/dryrun")
    ap.add_argument("--flash-blk", type=int, default=1024)
    args = ap.parse_args()
    res = run_cell(args.arch, args.shape, args.multi_pod, args.out_dir,
                   args.flash_blk)
    brief = {k: v for k, v in res.items()
             if k in ("arch", "shape", "mesh", "status", "compile_s", "wall_s",
                      "error", "reason")}
    print(json.dumps(brief))
    if res["status"] == "ok":
        print("memory_analysis:", json.dumps(res["memory"]))
        print("roofline:", json.dumps(res["roofline"]))


if __name__ == "__main__":
    main()
