import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count on first init); 512 placeholder host devices back both production
meshes.  For each cell this driver:

  1. builds the model and ShapeDtypeStruct inputs (no allocation),
  2. jits the right step (train_step / prefill / serve decode_step) with
     explicit in/out shardings from the logical rules,
  3. ``.lower().compile()`` — a sharding mismatch, compile-time OOM, or
     unsupported collective here is a bug in the framework,
  4. records memory_analysis, cost_analysis and the HLO collective bytes
     (trip-count-weighted) into a JSON cell report for §Dry-run / §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --mesh both
  python -m repro.launch.dryrun --all --out results/dryrun
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import set_mesh

from repro.configs import SHAPES, ARCH_NAMES, cell_status, get_config
from repro.distributed.partitioning import axis_rules, rules_for_mesh
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.roofline.analysis import analyze_compiled
from repro.train import AdamWConfig, make_train_step


def model_flops_estimate(cfg, sh) -> float:
    """6·N·D model FLOPs (dense) / 6·N_active·D (MoE); decode: D=batch·1."""
    n = cfg.active_param_count()
    if sh.kind == "train":
        return 6.0 * n * sh.tokens
    if sh.kind == "prefill":
        return 2.0 * n * sh.tokens
    return 2.0 * n * sh.global_batch  # decode: one token per sequence


def lower_cell(arch: str, shape: str, multi_pod: bool):
    cfg = get_config(arch)
    sh = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_for_mesh(mesh)
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    model = build_model(cfg)

    with axis_rules(rules, mesh_shape), set_mesh(mesh):
        if sh.kind == "train":
            state_shapes = S.train_state_shapes(model, cfg)
            state_shardings = S.train_state_shardings(mesh, state_shapes)
            batch_shapes = S.train_batch_shapes(cfg, sh)
            batch_shardings = S.batch_shardings(mesh, batch_shapes)
            # a microbatch must still divide the batch shards, or its batch
            # dim silently de-shards (replicates!) on the wider mesh — cap
            # grad-accum so each microbatch keeps ≥1 sample per batch shard
            batch_shards = 1
            for name in ("pod", "data"):
                batch_shards *= mesh_shape.get(name, 1)
            grad_accum = max(
                min(cfg.grad_accum, sh.global_batch // batch_shards), 1
            )
            step = make_train_step(
                model, AdamWConfig(), grad_accum=grad_accum
            )
            metrics_shardings = None  # infer: replicated scalars
            jitted = jax.jit(
                step,
                in_shardings=(state_shardings, batch_shardings),
                out_shardings=(state_shardings, metrics_shardings),
                donate_argnums=(0,),
            )
            lowered = jitted.lower(state_shapes, batch_shapes)
        elif sh.kind == "prefill":
            params_shapes = S.param_shapes(model, "bfloat16")  # serving dtype
            params_shardings = S.param_shardings(mesh, params_shapes)
            batch_shapes = S.prefill_batch_shapes(cfg, sh)
            batch_shardings = S.batch_shardings(mesh, batch_shapes)
            cache_sh = S.cache_shardings(
                mesh, jax.eval_shape(
                    lambda: model.init_cache(sh.global_batch, sh.seq_len)
                )
            )

            def prefill(params, batch):
                return model.prefill(params, batch, sh.seq_len)

            jitted = jax.jit(
                prefill,
                in_shardings=(params_shardings, batch_shardings),
                out_shardings=(None, cache_sh),
            )
            lowered = jitted.lower(params_shapes, batch_shapes)
        else:  # decode
            # §Perf iteration 5: decode weights are int8-quantized and
            # TP-only sharded — no weight all-gathers in the decode step
            from repro.models.layers import quantize_for_serving

            params_shapes = jax.eval_shape(
                quantize_for_serving, S.param_shapes(model, None)
            )
            params_shardings = S.param_shardings(mesh, params_shapes)
            cache_shapes = jax.eval_shape(
                lambda: model.init_cache(sh.global_batch, sh.seq_len)
            )
            cache_sh = S.cache_shardings(mesh, cache_shapes)
            tok_shapes = S.decode_token_shapes(cfg, sh)
            tok_shardings = S.batch_shardings(mesh, tok_shapes)

            def serve_step(params, cache, tokens, pos):
                return model.decode_step(params, cache, tokens, pos)

            jitted = jax.jit(
                serve_step,
                in_shardings=(
                    params_shardings, cache_sh, tok_shardings, S.replicated(mesh)
                ),
                out_shardings=(None, cache_sh),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(
                params_shapes, cache_shapes, tok_shapes,
                jax.ShapeDtypeStruct((), jnp.int32),
            )
        compiled = lowered.compile()
    return compiled, mesh, cfg, sh


def run_cell(arch: str, shape: str, multi_pod: bool, verbose: bool = True) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    status = cell_status(arch, shape)
    if status != "run":
        return {
            "arch": arch, "shape": shape, "mesh": mesh_name, "status": status,
        }
    t0 = time.time()
    compiled, mesh, cfg, sh = lower_cell(arch, shape, multi_pod)
    dt = time.time() - t0
    result = analyze_compiled(
        compiled, arch=arch, shape=shape, mesh_name=mesh_name,
        n_devices=mesh.devices.size,
        model_flops=model_flops_estimate(cfg, sh),
    )
    mem = compiled.memory_analysis()
    out = dataclasses.asdict(result)
    summary = result.summary()
    out["terms"] = {k: summary[k] for k in ("compute", "memory", "collective")}
    out["dominant"] = summary["dominant"]
    out["useful_flops_ratio"] = summary["useful_flops_ratio"]
    out["roofline_fraction"] = summary["roofline_fraction"]
    out["step_time_lower_bound_s"] = summary["step_time_lower_bound_s"]
    out["compile_seconds"] = dt
    if verbose:
        t = result.terms()
        print(
            f"[{mesh_name}] {arch} × {shape}: compile {dt:.1f}s  "
            f"compute {t['compute']*1e3:.2f}ms  memory {t['memory']*1e3:.2f}ms  "
            f"collective {t['collective']*1e3:.2f}ms  "
            f"dominant={max(t, key=t.get)}  "
            f"peak/device={out['memory']['peak_bytes']/2**30:.2f}GiB"
        )
        print("  memory_analysis:", str(mem).replace(chr(10), " ")[:300])
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--keep-going", action="store_true")
    args = ap.parse_args()

    archs = ARCH_NAMES if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            for multi_pod in meshes:
                mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
                fname = os.path.join(
                    args.out, f"{arch}__{shape}__{mesh_name}.json"
                )
                try:
                    out = run_cell(arch, shape, multi_pod)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    failures.append((arch, shape, mesh_name, str(e)))
                    if not args.keep_going:
                        raise
                    continue
                with open(fname, "w") as f:
                    json.dump(out, f, indent=1, default=str)
    if failures:
        print(f"\n{len(failures)} FAILED CELLS:")
        for f4 in failures:
            print("  ", *f4[:3], "->", f4[3][:200])
        raise SystemExit(1)
    print("\nDRY-RUN COMPLETE: all requested cells lowered + compiled.")


if __name__ == "__main__":
    main()
