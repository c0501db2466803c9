import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

For every (architecture × input shape × mesh) combination:
    lower → compile → memory_analysis (fits?) → cost_analysis + HLO parse
    (roofline terms, §Roofline), with the scan-depth correction of
    launch/roofline.py.

The XLA_FLAGS line above MUST precede any jax import — jax locks the device
count at first init; 512 host devices back both the 256-chip single-pod
mesh and the 2×256 multi-pod mesh.  Smoke tests / benches must NOT import
this module (they want 1 device).

Usage:
    python -m repro.launch.dryrun --arch gemma3-4b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--mixing circulant]
    python -m repro.launch.dryrun --all --both-meshes --out results/dryrun
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro.configs.base import get_config, list_archs
from repro.launch import roofline as rl
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_production_mesh
from repro.models.transformer import unit_size

# the chip the 256/512-chip pod meshes stand for (a roofline.PEAKS key); the
# host devices that back those meshes have no peaks of their own
TARGET_DEVICE_KIND = "TPU v5 lite"

# long_500k requires sub-quadratic state (DESIGN.md §4): native runners only
LONG_CONTEXT_ARCHS = {"gemma3_4b", "jamba_1p5_large_398b", "rwkv6_3b"}


def shape_applicable(arch: str, shape: str) -> bool:
    if shape == "long_500k":
        return _norm(arch) in LONG_CONTEXT_ARCHS
    return True


def _norm(arch: str) -> str:
    return arch.replace("-", "_").replace(".", "p")


def run_one(
    arch: str,
    shape: str,
    *,
    multi_pod: bool = False,
    mixing: str = "dense",
    skip_cost_extrapolation: bool = False,
    cfg_override=None,
    variant: dict | None = None,
) -> dict:
    """Lower + compile one combination; return the §Dry-run/§Roofline record.

    ``variant``: §Perf config overrides, e.g. {"attn_impl": "chunked",
    "swa_impl": "blocked", "attn_weight_sharding": "replicate"}.
    """
    cfg = cfg_override if cfg_override is not None else get_config(arch)
    if variant:
        cfg = dataclasses.replace(cfg, **variant)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec: dict = {
        "arch": cfg.name,
        "shape": shape,
        "mesh": mesh_name,
        "mixing": mixing if shape == "train_4k" else None,
        "variant": variant or {},
        "status": "unknown",
    }
    t0 = time.time()
    try:
        with mesh:
            step, args, in_sh, out_sh = steps_mod.build(
                cfg, shape, mesh, multi_pod=multi_pod, mixing=mixing
            )
            lowered = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh).lower(*args)
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
            rec["lower_compile_s"] = round(time.time() - t0, 1)
            rec["memory_analysis"] = {
                k: int(getattr(mem, k))
                for k in (
                    "argument_size_in_bytes",
                    "output_size_in_bytes",
                    "temp_size_in_bytes",
                    "generated_code_size_in_bytes",
                )
                if hasattr(mem, k)
            }
            full_terms = rl.terms_from_costs(cost, hlo, TARGET_DEVICE_KIND)
            rec["raw_terms_scan_body_once"] = full_terms.as_dict()

            # ---- scan-depth-corrected roofline terms ------------------
            # XLA cost analysis counts while bodies once; the corrected
            # terms come from small UNROLLED lowerings + exact polynomial
            # extrapolation (launch/roofline.py).
            u = unit_size(cfg)
            tail = cfg.n_layers % u
            n_full = cfg.n_layers // u
            kind = steps_mod.SHAPES[shape].kind
            if skip_cost_extrapolation or n_full <= 2:
                terms = full_terms
            elif kind == "decode":
                # no inner sequence scans on the decode path → depth-only
                sub = []
                for periods in (1, 2):
                    cfg_t = dataclasses.replace(cfg, n_layers=periods * u + tail)
                    step_t, args_t, in_t, out_t = steps_mod.build(
                        cfg_t, shape, mesh, multi_pod=multi_pod, mixing=mixing
                    )
                    comp_t = (
                        jax.jit(step_t, in_shardings=in_t, out_shardings=out_t).lower(*args_t).compile()
                    )
                    sub.append(rl.terms_from_costs(comp_t.cost_analysis(), comp_t.as_text(), TARGET_DEVICE_KIND))
                terms = rl.extrapolate_depth(sub[0], sub[1], n_full)
            else:
                # train/prefill: 6-point (period × seq) fit with unrolled
                # inner chunk scans; costs are exact polynomials in S
                seq_target = steps_mod.SHAPES[shape].seq_len
                points = {}
                # blocked-SWA only activates for S > window: fit above it
                if cfg.swa_impl == "blocked" and cfg.sliding_window >= 256:
                    w = cfg.sliding_window
                    s_points = (2 * w, 4 * w, 8 * w) if 8 * w <= seq_target else (2 * w, 3 * w, 4 * w)
                else:
                    s_points = tuple(s for s in (256, 512, 1024, 2048) if s <= seq_target)
                for periods in (1, 2):
                    for s in s_points:
                        nf_scaled = 0
                        if cfg.n_frontend_tokens:
                            nf_scaled = max(8, (cfg.n_frontend_tokens * s // seq_target) // 8 * 8)
                        cfg_t = dataclasses.replace(
                            cfg,
                            n_layers=periods * u + tail,
                            unroll_scans=True,
                            n_frontend_tokens=nf_scaled,
                        )
                        step_t, args_t, in_t, out_t = steps_mod.build(
                            cfg_t, shape, mesh, multi_pod=multi_pod, mixing=mixing, seq_len=s
                        )
                        comp_t = (
                            jax.jit(step_t, in_shardings=in_t, out_shardings=out_t)
                            .lower(*args_t)
                            .compile()
                        )
                        points[(periods, s)] = rl.terms_from_costs(comp_t.cost_analysis(), comp_t.as_text(), TARGET_DEVICE_KIND)
                # frontend tokens scale with S in the fit; correct the target
                # text length implicitly via seq_target evaluation
                terms = rl.extrapolate_depth_and_seq(points, n_full, seq_target)
            rec["terms"] = terms.as_dict()

            # ---- MODEL_FLOPS ratio ------------------------------------
            sh = steps_mod.SHAPES[shape]
            if sh.kind == "train":
                tokens = sh.global_batch * sh.seq_len
            elif sh.kind == "prefill":
                tokens = sh.global_batch * sh.seq_len
            else:
                tokens = sh.global_batch  # ONE new token per sequence
            chips = 512 if multi_pod else 256
            mf = rl.model_flops(cfg.n_active_params(), tokens, sh.kind)
            rec["model_flops"] = mf
            rec["hlo_flops_total"] = terms.flops * chips
            rec["useful_flops_ratio"] = mf / max(terms.flops * chips, 1.0)
            rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["wall_s"] = round(time.time() - t0, 1)
    return rec


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", type=str, default=None)
    p.add_argument("--shape", type=str, default=None, choices=[*steps_mod.SHAPES, None])
    p.add_argument("--all", action="store_true", help="sweep all (arch × applicable shape)")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--both-meshes", action="store_true")
    p.add_argument("--mixing", type=str, default="dense", choices=["dense", "circulant"])
    p.add_argument("--out", type=str, default="results/dryrun")
    p.add_argument("--skip-extrapolation", action="store_true")
    p.add_argument("--attn-impl", type=str, default=None, choices=["full", "chunked"])
    p.add_argument("--swa-impl", type=str, default=None, choices=["full", "blocked"])
    p.add_argument("--attn-sharding", type=str, default=None, choices=["auto", "replicate", "qkv_split"])
    p.add_argument("--tag", type=str, default=None, help="suffix for result filenames")
    p.add_argument(
        "--sliding-window", type=int, default=None,
        help="beyond-paper demo: force all layers to sliding-window attention "
        "of this size (enables long_500k for dense archs; DESIGN.md §4)",
    )
    args = p.parse_args()

    variant = {}
    if args.attn_impl:
        variant["attn_impl"] = args.attn_impl
    if args.swa_impl:
        variant["swa_impl"] = args.swa_impl
    if args.attn_sharding:
        variant["attn_weight_sharding"] = args.attn_sharding
    if args.sliding_window:
        variant["block_pattern"] = ("swa",)
        variant["sliding_window"] = args.sliding_window
        variant["max_seq_len"] = 524288

    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(steps_mod.SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    for arch in archs:
        for shape in shapes:
            if not shape_applicable(arch, shape) and "sliding_window" not in variant:
                print(f"SKIP  {arch:28s} {shape:12s} (long-context inapplicable, see DESIGN.md)")
                continue
            for mp in meshes:
                rec = run_one(arch, shape, multi_pod=mp, mixing=args.mixing,
                              skip_cost_extrapolation=args.skip_extrapolation,
                              variant=variant or None)
                mesh_name = rec["mesh"]
                tag = f"{_norm(arch)}__{shape}__{mesh_name}" + (
                    f"__{args.mixing}" if shape == "train_4k" and args.mixing != "dense" else ""
                ) + (f"__{args.tag}" if args.tag else "")
                path = os.path.join(args.out, tag + ".json")
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    t = rec["terms"]
                    extra = (
                        f"dom={t['dominant']:10s} comp={t['compute_s']:.2e}s "
                        f"mem={t['memory_s']:.2e}s coll={t['collective_s']:.2e}s "
                        f"useful={rec['useful_flops_ratio']:.2f}"
                    )
                else:
                    extra = rec["error"][:120]
                print(f"{status.upper():5s} {arch:28s} {shape:12s} {mesh_name:10s} "
                      f"{rec['wall_s']:6.1f}s {extra}", flush=True)


if __name__ == "__main__":
    main()
