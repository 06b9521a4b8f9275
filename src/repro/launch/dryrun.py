import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: prove the distribution config is coherent without
hardware.

For every (architecture x input shape) cell, AOT-lower and compile the
step function (train_step / prefill / decode as the shape dictates) on the
single-pod 16x16 mesh and the 2x16x16 multi-pod mesh, then record:

  * ``compiled.memory_analysis()`` — fits-per-device evidence,
  * ``compiled.cost_analysis()``   — per-device FLOPs / bytes,
  * parsed collective bytes        — the third roofline term,
  * sharding fallbacks             — dims that replicated (divisibility).

Results land in ``results/dryrun/<arch>__<shape>__<mesh>[__variant].json`` and
feed the roofline section of ``benchmarks.run``.

NOTE: the two lines above MUST run before any other import — jax locks the
device count on first init. Do not set this flag globally: smoke tests and
benches must see 1 device.
"""
import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax

from repro.configs import (ARCH_IDS, SHAPES_BY_NAME, OptimizerConfig,
                           applicable_shapes, get_model_config,
                           get_optimized_config)
from repro.launch import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import extract_terms, model_flops
from repro.launch.steps import lower_step_for, lower_train_step
from repro.models.api import build_model

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def cell_rules(shape_name: str) -> Optional[Dict]:
    """Axis-rule overrides per cell. long_500k decodes context-parallel:
    the KV/state sequence dim shards over the `data` axis."""
    if shape_name == "long_500k":
        return {"seq": "data"}
    return None


def reduced_depth(cfg, k: int):
    """Config with ``prefix + k`` scan periods (and a proportionally reduced
    encoder). Used for exact per-period cost extrapolation: XLA's
    ``cost_analysis`` counts a while-loop body ONCE, so the full-depth module
    underreports FLOPs/bytes by ~n_periods; lowering k=1 and k=2 and taking
    the difference isolates one period exactly (scan bodies are identical).
    """
    from repro.models.transformer import layer_layout
    prefix, kinds, n_periods = layer_layout(cfg)
    P = len(kinds)
    kw = {"num_layers": prefix + k * P, "scan_layers": False}
    if cfg.num_encoder_layers:
        enc_per = max(1, cfg.num_encoder_layers // n_periods)
        kw["num_encoder_layers"] = k * enc_per
    return cfg.replace(**kw), n_periods


from repro.launch.dryrun_variants import apply_variant_pure


def apply_variant(cfg, variant: str):
    """See repro.launch.dryrun_variants.apply_variant_pure."""
    return apply_variant_pure(cfg, variant)


def _lower_variant(model, opt_cfg, mesh, shape, mb: int, int8pod: bool):
    if int8pod:
        from repro.launch.compressed import lower_compressed_train_step
        assert shape.kind == "train", "int8pod applies to train cells"
        return lower_compressed_train_step(model, opt_cfg, mesh, shape)
    if shape.kind == "train" and mb > 1:
        return lower_train_step(model, opt_cfg, mesh, shape,
                                microbatches=mb)
    return lower_step_for(model, opt_cfg, mesh, shape)


def _cost_of(model, opt_cfg, mesh, shape, mb: int = 1,
             int8pod: bool = False) -> Dict[str, float]:
    # Single-trip attention scan so cost_analysis sees the full SDPA work
    # (it counts while-loop bodies once). SSM recurrence inner scans stay
    # chunked: their FLOPs are ~1% of a layer (projections dominate), so
    # the residual undercount is immaterial.
    prev = os.environ.get("REPRO_ATTN_BLOCK_K")
    prev_cm = os.environ.get("REPRO_COST_MODE")
    os.environ["REPRO_ATTN_BLOCK_K"] = str(max(shape.seq_len, 512))
    os.environ["REPRO_COST_MODE"] = "1"
    try:
        lowered, _ = _lower_variant(model, opt_cfg, mesh, shape, mb,
                                    int8pod)
        compiled = lowered.compile()
        hlo = compiled.as_text()
        terms, coll = extract_terms(compiled, chips=mesh.size, hlo_text=hlo)
    finally:
        if prev is None:
            os.environ.pop("REPRO_ATTN_BLOCK_K", None)
        else:
            os.environ["REPRO_ATTN_BLOCK_K"] = prev
        if prev_cm is None:
            os.environ.pop("REPRO_COST_MODE", None)
        else:
            os.environ["REPRO_COST_MODE"] = prev_cm
    return {"flops": terms.flops_per_device,
            "bytes": terms.bytes_per_device,
            "coll": terms.collective_bytes_per_device}


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             optimized: bool = False, variant: str = "",
             out_dir: str = RESULTS_DIR,
             save: bool = True, extrapolate: bool = True) -> Dict:
    shape = SHAPES_BY_NAME[shape_name]
    if optimized and not variant:
        variant = "opt"
    cfg, mb, int8pod, noz1, vrules, venv = apply_variant(
        get_model_config(arch), variant)
    mesh_name = "multi" if multi_pod else "single"
    tag = f"{arch}__{shape_name}__{mesh_name}" + \
        (f"__{variant.replace('+', '_')}" if variant else "")
    t0 = time.time()
    result: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                    "variant": variant}
    prev_env = {k: os.environ.get(k) for k in venv}
    os.environ.update(venv)
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        model = build_model(cfg)
        opt_cfg = OptimizerConfig(zero1=not noz1)
        rules = dict(cell_rules(shape_name) or {})
        rules.update(vrules)
        with mesh, shd.axis_rules(mesh, rules or None):
            # 1) full-depth compile: proves the cell lowers+compiles, gives
            #    memory analysis and the collective schedule.
            lowered, _ = _lower_variant(model, opt_cfg, mesh, shape, mb,
                                        int8pod)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            hlo = compiled.as_text()
            terms, coll = extract_terms(compiled, chips=mesh.size,
                                        hlo_text=hlo)
            mem = compiled.memory_analysis()
            mem_info = {}
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                mem_info[k] = int(getattr(mem, k, 0))

            # 2) per-period cost extrapolation (scan bodies counted once by
            #    cost_analysis): cost(full) = c1 + (n_periods-1) * (c2-c1).
            from repro.launch.roofline import RooflineTerms
            if extrapolate:
                cfg1, n_per = reduced_depth(cfg, 1)
                cfg2, _ = reduced_depth(cfg, 2)
                c1 = _cost_of(build_model(cfg1), opt_cfg, mesh, shape,
                              mb, int8pod)
                c2 = _cost_of(build_model(cfg2), opt_cfg, mesh, shape,
                              mb, int8pod)
                full = {k: c1[k] + (n_per - 1) * max(0.0, c2[k] - c1[k])
                        for k in c1}
                terms = RooflineTerms(
                    flops_per_device=full["flops"],
                    bytes_per_device=full["bytes"],
                    collective_bytes_per_device=full["coll"],
                    chips=mesh.size)
                result["cost_extrapolation"] = {
                    "n_periods": n_per, "c1": c1, "c2": c2}

            mf = model_flops(cfg, shape)
            hlo_flops_global = terms.flops_per_device * mesh.size
            result.update({
                "ok": True,
                "chips": mesh.size,
                "lower_s": round(t_lower, 2),
                "compile_s": round(t_compile, 2),
                "memory_analysis": mem_info,
                "roofline": terms.to_dict(),
                "collectives": coll,
                "model_flops_global": mf,
                "useful_flops_ratio": (mf / hlo_flops_global
                                       if hlo_flops_global else 0.0),
                "fallbacks": [list(f) for f in set(shd.fallbacks())],
            })
    except Exception as e:  # noqa: BLE001 — dry-run reports failures
        result.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]})
    for k, v in prev_env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    result["wall_s"] = round(time.time() - t0, 2)
    if save:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(result, f, indent=1, default=str)
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="shape name (default: all "
                                                  "applicable)")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--optimized", action="store_true",
                    help="use the beyond-paper optimized config variant")
    ap.add_argument("--variant", default="",
                    help="'+'-separated: opt, mb<k>, lc<n>, int8pod")
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCH_IDS
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    failures = 0
    for arch in archs:
        shapes = ([args.shape] if args.shape
                  else [s.name for s in applicable_shapes(arch)])
        for shape_name in shapes:
            for mesh_name in meshes:
                v = args.variant or ("opt" if args.optimized else "")
                tag = f"{arch}__{shape_name}__{mesh_name}" + \
                    (f"__{v.replace('+', '_')}" if v else "")
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        if json.load(f).get("ok"):
                            print(f"[skip] {tag}")
                            continue
                r = run_cell(arch, shape_name,
                             multi_pod=(mesh_name == "multi"),
                             optimized=args.optimized,
                             variant=args.variant, out_dir=args.out)
                if r["ok"]:
                    t = r["roofline"]
                    print(f"[ok]   {tag}: compile {r['compile_s']}s "
                          f"compute {t['compute_s']:.4f}s "
                          f"memory {t['memory_s']:.4f}s "
                          f"collective {t['collective_s']:.4f}s "
                          f"dominant={t['dominant']}")
                else:
                    failures += 1
                    print(f"[FAIL] {tag}: {r['error']}")
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")


if __name__ == "__main__":
    main()
