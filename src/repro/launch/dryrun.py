import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST precede every other import — jax locks the
# device count on first init, and the production meshes below need 512
# placeholder host devices. Only this module sets the flag; smoke tests and
# benchmarks see the single real CPU device.

"""Multi-pod dry-run driver.

For every (architecture x input-shape x mesh) cell: resolve the sharding
profile, build allocation-free abstract inputs (ShapeDtypeStruct), then
``jax.jit(step).lower(...).compile()`` and record memory/cost analysis plus
the collective schedule parsed from the optimized per-device HLO. Failures
(sharding mismatch, OOM-at-compile, unsupported collective) are bugs in the
system, not in the driver.

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun.jsonl
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo import collective_wire_bytes
from repro.analysis.memory_est import estimate_hbm
from repro.analysis.roofline import model_flops, roofline_terms
from repro.models.scan_utils import scan_unroll
from repro.configs import ARCHITECTURES, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_step
from repro.models import (
    SHAPES,
    abstract_params,
    cache_descs,
    param_descs,
    shape_by_name,
)
from repro.models.params import is_desc, resolve_specs
from repro.parallel.sharding import (
    batch_dtypes,
    batch_input_descs,
    mesh_axis_sizes,
    profile_for,
    tree_shardings,
)


def scaled_pair(cfg):
    """Two pattern-preserving shallow variants for cost extrapolation.

    XLA's HloCostAnalysis counts a while-loop body ONCE regardless of trip
    count (verified empirically), so scanned stacks under-report flops/bytes/
    collectives. The probes lower with fully UNROLLED stacks (scan_unroll
    context) on (small, large) configs differing by exactly one repeated
    unit of the stack pattern, then extrapolate linearly:
        cost(full) = cost(small) + extra_units * (cost(large) - cost(small))
    This is exact: the HLO of the repeated unit is identical at any depth.
    Returns (small_cfg, large_cfg, extra_units).
    """
    import dataclasses as dc

    if cfg.family == "encdec":
        assert cfg.encoder_layers == cfg.num_layers
        small = dc.replace(cfg, num_layers=2, encoder_layers=2)
        large = dc.replace(cfg, num_layers=4, encoder_layers=4)
        return small, large, (cfg.num_layers - 2) // 2
    if cfg.global_period:  # gemma3 pattern: groups of p + tail
        p = cfg.global_period
        tail = cfg.num_layers % p
        small = dc.replace(cfg, num_layers=p + tail)
        large = dc.replace(cfg, num_layers=2 * p + tail)
        return small, large, (cfg.num_layers - (p + tail)) // p
    if cfg.moe is not None and cfg.moe.first_k_dense:
        fk = cfg.moe.first_k_dense
        small = dc.replace(cfg, num_layers=fk + 2)
        large = dc.replace(cfg, num_layers=fk + 4)
        return small, large, (cfg.num_layers - fk - 2) // 2
    if cfg.family == "hybrid":
        # the unit is a site and the Mamba layers up to the next one; its
        # length varies by a layer or two along zamba2's stack, so this
        # extrapolation is approximate there
        ids = cfg.hybrid_layer_ids
        small = dc.replace(cfg, num_layers=ids[1], hybrid_layer_ids=ids[:1])
        large = dc.replace(cfg, num_layers=ids[2], hybrid_layer_ids=ids[:2])
        return small, large, len(ids) - 1
    if cfg.family == "vlm":
        p = cfg.cross_attn_period
        small = dc.replace(cfg, num_layers=p)
        large = dc.replace(cfg, num_layers=2 * p)
        return small, large, (cfg.num_layers - p) // p
    small = dc.replace(cfg, num_layers=2)
    large = dc.replace(cfg, num_layers=4)
    return small, large, (cfg.num_layers - 2) // 2


def extrapolate(small: dict, large: dict, extra: int) -> dict:
    """Linear two-point extrapolation, clamped at the small-probe value:
    GSPMD occasionally picks a cheaper collective strategy at depth (slope
    < 0), in which case the shallow probe is the conservative bound."""
    keys = set(small) | set(large)
    out = {}
    for k in keys:
        s = small.get(k, 0.0)
        l = large.get(k, 0.0)
        if not isinstance(s, (int, float)):
            continue
        v = s + extra * (l - s)
        out[k] = max(v, min(s, l), 0.0)
    return out


def skip_reason(cfg, shape) -> str:
    if shape.name == "long_500k" and not cfg.supports_long_context():
        return (
            "pure full-attention arch: 500k-token KV per layer is architecturally "
            "a non-goal (sub-quadratic archs run this cell; see DESIGN.md §4)"
        )
    return ""


def _abstract(descs, dtype):
    return jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(d.shape, dtype), descs, is_leaf=is_desc
    )


def _abstract_batch(bdescs, dtypes):
    return {
        k: jax.ShapeDtypeStruct(d.shape, dtypes.get(k, jnp.int32))
        for k, d in bdescs.items()
    }


def _compile_cell(cfg, shape, mesh, remat: str):
    """Lower + compile one (cfg, shape) on mesh; returns the Compiled."""
    profile = profile_for(cfg, shape, mesh)
    pdescs = param_descs(cfg)
    p_abs = abstract_params(pdescs, jnp.bfloat16)
    p_shard = tree_shardings(pdescs, profile, mesh)
    bdescs = batch_input_descs(cfg, shape)
    b_abs = _abstract_batch(bdescs, batch_dtypes(cfg))
    b_shard = tree_shardings(bdescs, profile, mesh)
    scalar_shard = NamedSharding(mesh, P())

    from repro.parallel.ep_moe import ep_mesh

    step = make_step(cfg, shape.kind, remat=remat)
    with mesh, ep_mesh(mesh):
        if shape.kind == "train":
            opt_abs = {
                "m": _abstract(pdescs, jnp.float32),
                "v": _abstract(pdescs, jnp.float32),
                "step": jax.ShapeDtypeStruct((), jnp.int32),
            }
            opt_shard = {"m": p_shard, "v": p_shard, "step": scalar_shard}
            jitted = jax.jit(
                step,
                in_shardings=(p_shard, opt_shard, b_shard),
                out_shardings=(p_shard, opt_shard, scalar_shard),
            )
            lowered = jitted.lower(p_abs, opt_abs, b_abs)
        elif shape.kind == "prefill":
            jitted = jax.jit(step, in_shardings=(p_shard, b_shard))
            lowered = jitted.lower(p_abs, b_abs)
        else:  # decode
            cdescs = cache_descs(cfg, batch=shape.global_batch, max_len=shape.seq_len)
            c_abs = _abstract(cdescs, jnp.bfloat16)
            c_shard = tree_shardings(cdescs, profile, mesh)
            jitted = jax.jit(
                step,
                in_shardings=(p_shard, c_shard, b_shard, scalar_shard),
                out_shardings=(None, c_shard),
                donate_argnums=(1,),  # serve loops donate the cache: the
                # dynamic-update-slice becomes in-place, not a full copy
            )
            lowered = jitted.lower(
                p_abs, c_abs, b_abs, jax.ShapeDtypeStruct((), jnp.int32)
            )
        return lowered.compile(), profile


def _cost_and_collectives(compiled):
    cost = compiled.cost_analysis() or {}
    cost = {
        k: float(v)
        for k, v in cost.items()
        if k == "flops" or k.startswith("bytes accessed")
    }
    coll = collective_wire_bytes(compiled.as_text())
    return cost, coll


def build_cell(arch: str, shape_name: str, multi_pod: bool, remat: str = "full",
               variant: str = "baseline", tune: dict = None):
    """Lower + compile one cell; returns the result record.

    The FULL config is compiled (the deliverable: sharding coherence + memory
    analysis); flops/bytes/collectives are two-point extrapolated from
    pattern-preserving shallow variants because HloCostAnalysis counts scan
    bodies once (see scaled_pair). ``tune`` applies §Perf knobs
    (models/tuning.py) and tags the record with ``variant``."""
    from repro.models.tuning import tuning

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    cfg = get_config(arch)
    shape = shape_by_name(shape_name)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "variant": variant,
    }
    _tuning_ctx = tuning(**(tune or {}))
    reason = skip_reason(cfg, shape)
    if reason:
        rec.update(status="skipped", reason=reason)
        return rec

    # 1) full-config compile: the coherence proof + raw XLA memory numbers
    t0 = time.time()
    with _tuning_ctx:
        compiled, profile = _compile_cell(cfg, shape, mesh, remat)
    rec.update(status="ok", compile_s=round(time.time() - t0, 2), profile=profile.name)
    try:
        mem = compiled.memory_analysis()
        # NOTE: the CPU backend has no buffer liveness: temp ~= bytes
        # accessed. Recorded raw; the fits-in-HBM proof is memory_est below.
        rec["memory_xla_raw"] = {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes_no_liveness": int(getattr(mem, "temp_size_in_bytes", 0)),
        }
    except Exception as e:  # pragma: no cover
        rec["memory_xla_raw"] = {"unavailable": str(e)}
    with tuning(**(tune or {})):
        rec["memory_est"] = {
            k: (round(v, 4) if isinstance(v, float) else v)
            for k, v in estimate_hbm(
                cfg, shape, profile.rules, mesh_axis_sizes(mesh), remat
            ).items()
        }

    # 2) cost terms: two-point extrapolation over UNROLLED shallow probes
    small, large, extra = scaled_pair(cfg)
    with tuning(**(tune or {})), scan_unroll():
        c_small, _ = _compile_cell(small, shape, mesh, remat)
        c_large, _ = _compile_cell(large, shape, mesh, remat)
    cost_s, coll_s = _cost_and_collectives(c_small)
    cost_l, coll_l = _cost_and_collectives(c_large)
    rec["cost"] = extrapolate(cost_s, cost_l, extra)
    rec["collectives"] = {
        k: round(v, 1) for k, v in extrapolate(coll_s, coll_l, extra).items()
    }
    rec["cost_method"] = f"two-point unrolled extrapolation (+{extra} units)"

    rec["roofline"] = {
        k: (round(v, 6) if isinstance(v, float) else v)
        for k, v in roofline_terms(
            rec["cost"], rec["collectives"], cfg, shape, chips
        ).items()
    }
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=[s.name for s in SHAPES] + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--remat", default="full", choices=["none", "dots", "full"])
    ap.add_argument("--out", default=None, help="append JSONL results here")
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    # §Perf tuning knobs (models/tuning.py); tag runs with --variant
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--loss-chunk", type=int, default=0)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--decode-seq-constraint", action="store_true")
    ap.add_argument("--constrain-activations", action="store_true")
    ap.add_argument("--moe-impl", default="einsum", choices=["einsum", "ep"])
    args = ap.parse_args()
    tune = dict(
        loss_chunk=args.loss_chunk,
        microbatch=args.microbatch,
        decode_seq_constraint=args.decode_seq_constraint,
        constrain_activations=args.constrain_activations,
        moe_impl=args.moe_impl,
    )

    archs = ARCHITECTURES if (args.all or not args.arch) else [args.arch]
    shapes = [s.name for s in SHAPES] if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    done = set()
    out_path = Path(args.out) if args.out else None
    if out_path and out_path.exists() and not args.force:
        for line in out_path.read_text().splitlines():
            try:
                r = json.loads(line)
                done.add((r["arch"], r["shape"], r["mesh"], r.get("variant", "baseline")))
            except Exception:
                pass

    n_ok = n_skip = n_fail = 0
    for multi_pod in meshes:
        mesh_name = "2x16x16" if multi_pod else "16x16"
        for arch in archs:
            for shape_name in shapes:
                key = (arch, shape_name, mesh_name, args.variant)
                if key in done:
                    continue
                try:
                    rec = build_cell(
                        arch, shape_name, multi_pod,
                        remat=args.remat, variant=args.variant, tune=tune,
                    )
                except Exception:
                    rec = {
                        "arch": arch, "shape": shape_name, "mesh": mesh_name,
                        "variant": args.variant,
                        "status": "failed", "error": traceback.format_exc(limit=4),
                    }
                st = rec["status"]
                n_ok += st == "ok"
                n_skip += st == "skipped"
                n_fail += st == "failed"
                line = json.dumps(rec)
                if out_path:
                    out_path.parent.mkdir(parents=True, exist_ok=True)
                    with open(out_path, "a") as f:
                        f.write(line + "\n")
                brief = {k: rec.get(k) for k in ("arch", "shape", "mesh", "status", "compile_s")}
                if st == "ok":
                    brief["dominant"] = rec["roofline"]["dominant"]
                    brief["roofline_fraction"] = rec["roofline"]["roofline_fraction"]
                    # proves it fits / cost source for §Roofline:
                    brief["hbm_frac"] = rec["memory_est"]["hbm_fraction"]
                    brief["fits_16g"] = rec["memory_est"]["fits_16g"]
                    brief["flops_per_chip"] = rec["cost"].get("flops")
                print(json.dumps(brief), flush=True)
                if st == "failed":
                    print(rec["error"], flush=True)
    print(f"dryrun: ok={n_ok} skipped={n_skip} failed={n_fail}", flush=True)
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
