import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# NOTE: the two lines above MUST run before any other import (jax locks the
# device count on first init) — hence no `from __future__` in this module.
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this script:
  1. builds the production mesh (16×16 single-pod / 2×16×16 multi-pod),
  2. lowers ``train_step`` (train/prefill shapes) or ``serve_step``
     (decode shapes) against ShapeDtypeStruct inputs (no allocation),
  3. compiles, prints ``memory_analysis()`` (proves it fits) and
     ``cost_analysis()`` (FLOPs/bytes for the roofline),
  4. parses the post-SPMD HLO for collective operand bytes,
  5. writes a JSON record to experiments/dryrun/.

Usage:
  python -m repro.launch.dryrun --arch codeqwen1.5-7b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import dataclasses
import json
import pathlib
import sys
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, all_configs, get_config
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_serve_step, make_train_step
from repro.models import build, input_specs, supports_shape
from repro.optim import AdamWConfig, opt_state_specs
from repro.parallel import partition

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

def ns(tree, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               cfg_overrides: dict | None = None, verbose: bool = True,
               num_microbatches: int | None = None):
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    ok, why = supports_shape(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why,
                "mesh": "2x16x16" if multi_pod else "16x16"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build(cfg)
    t0 = time.time()

    with jax.set_mesh(mesh):
        batch = input_specs(cfg, shape)
        if shape.mode == "prefill":
            # serving prefill: populate decode caches from the prompt batch
            # (VLM prompts carry an image-token prefix in the cache)
            extra = cfg.n_img_tokens if cfg.family == "vlm" else 0
            params_shape = jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0)))
            pspecs = partition.param_specs(params_shape, mesh)
            cache_shape = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch,
                                         shape.seq_len + extra))
            cspecs = partition.cache_specs_tree(cache_shape, mesh)
            jitted = jax.jit(
                lambda p, c, b: model.prefill(p, c, b),
                in_shardings=(ns(pspecs, mesh), ns(cspecs, mesh),
                              ns(partition.batch_specs(batch, mesh), mesh)),
                out_shardings=(None, ns(cspecs, mesh)),
                donate_argnums=(1,))
            lowered = jitted.lower(params_shape, cache_shape, batch)
        elif shape.mode == "train":
            opt_cfg = AdamWConfig()
            params_shape = jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0)))
            pspecs = partition.param_specs(params_shape, mesh)
            state_specs = {"params": pspecs,
                           "opt": opt_state_specs(pspecs, opt_cfg)}
            from repro.optim import init_opt_state
            state_shape = jax.eval_shape(
                lambda p: {"params": p, "opt": init_opt_state(p, opt_cfg)},
                params_shape)
            batch_specs = partition.batch_specs(batch, mesh)
            # gradient accumulation: keep ~2 sequences per device per microbatch
            dp = mesh.shape.get("pod", 1) * mesh.shape.get("data", 1)
            per_dev = max(1, shape.global_batch // dp)
            micro = num_microbatches or max(1, min(8, per_dev // 2))
            while shape.global_batch % (micro * dp) and micro > 1:
                micro -= 1
            step = make_train_step(cfg, mesh, opt_cfg, num_microbatches=micro)
            jitted = jax.jit(
                step,
                in_shardings=(ns(state_specs, mesh), ns(batch_specs, mesh)),
                out_shardings=(ns(state_specs, mesh), None),
                donate_argnums=(0,))
            lowered = jitted.lower(state_shape, batch)
        else:  # decode
            params_shape = jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0)))
            pspecs = partition.param_specs(params_shape, mesh)
            cache_shape = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            cspecs = partition.cache_specs_tree(cache_shape, mesh)
            step = make_serve_step(cfg, mesh)
            jitted = jax.jit(
                step,
                in_shardings=(ns(pspecs, mesh), ns(cspecs, mesh),
                              ns(partition.batch_specs(batch["tokens"], mesh), mesh),
                              None),
                out_shardings=(None, ns(cspecs, mesh)),
                donate_argnums=(1,))
            lowered = jitted.lower(params_shape, cache_shape,
                                   batch["tokens"], batch["pos"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    t2 = time.time()
    hlo = compiled.as_text()
    corrected = hlo_analysis.analyze(hlo)      # trip-count-corrected, per device
    t_analyze = time.time() - t2
    coll = corrected["collectives"]
    n_dev = mesh.devices.size

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mode": shape.mode, "devices": n_dev,
        "num_microbatches": locals().get("micro", 1),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "analyze_s": round(t_analyze, 1),
        # raw XLA numbers (loop bodies counted once — kept for reference)
        "xla_flops_per_device": float(cost.get("flops", -1)),
        "xla_bytes_per_device": float(cost.get("bytes accessed", -1)),
        # trip-count-corrected numbers (see launch/hlo_analysis.py)
        "flops_per_device": corrected["flops"],
        "bytes_per_device": corrected["bytes"],
        "collective_bytes_per_device": coll,
        "trip_count_unknown": corrected.get("trip_count_unknown", False),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
    }
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {rec['mesh']}: "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s")
        print(f"  memory_analysis: {rec['memory']}")
        print(f"  corrected: flops/dev={rec['flops_per_device']:.3e} "
              f"bytes/dev={rec['bytes_per_device']:.3e} "
              f"(xla once-counted: {rec['xla_flops_per_device']:.3e})")
        print(f"  collectives: { {k: (f'{v:.3e}' if isinstance(v, float) else v) for k, v in coll.items()} }")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--attn-impl", default=None,
                    help="override cfg.attn_impl: 'auto' or any "
                         "repro.attention registry backend name (legacy "
                         "'sparse'/'kernel' aliases still resolve)")
    ap.add_argument("--q-chunk", type=int, default=None)
    ap.add_argument("--set", action="append", default=[],
                    help="generic ModelConfig override key=value (python "
                         "literal), e.g. --set remat=False")
    ap.add_argument("--micro", type=int, default=None,
                    help="override num_microbatches")
    ap.add_argument("--tag", default=None,
                    help="write result as <tag>.json (perf experiments)")
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args()

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    archs = list(all_configs()) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]
    overrides = {}
    if args.attn_impl:
        overrides["attn_impl"] = args.attn_impl
    if args.q_chunk:
        overrides["q_chunk"] = args.q_chunk
    import ast
    for kv in args.set:
        key, val = kv.split("=", 1)
        try:
            overrides[key] = ast.literal_eval(val)
        except (ValueError, SyntaxError):
            overrides[key] = val

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = args.tag or f"{arch}_{shape}_{'mp' if mp else 'sp'}"
                path = outdir / f"{tag}.json"
                if path.exists():
                    print(f"[dryrun] skip existing {tag}")
                    continue
                try:
                    rec = lower_cell(arch, shape, mp, overrides or None,
                                     num_microbatches=args.micro)
                except Exception as e:  # noqa: BLE001 — record and continue
                    failures += 1
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-4000:]}
                    print(f"[dryrun] FAIL {tag}: {rec['error']}")
                path.write_text(json.dumps(rec, indent=1))
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
