"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the real step function (train_step for train
shapes, prefill/serve_step for inference shapes), lowers it with
ShapeDtypeStruct stand-ins (zero allocation), compiles it for the
production mesh, and records:

* ``memory_analysis()``  — per-device argument/output/temp bytes (fits-HBM proof)
* ``cost_analysis()``    — HLO FLOPs + bytes for the roofline terms
* collective bytes       — parsed from the partitioned HLO (hlo_analysis)
* MODEL_FLOPS = 6·N·D    — the useful-compute yardstick

Artifacts land in experiments/dryrun/<arch>__<shape>__<mesh>.json.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-14b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import dataclasses
import json
import os
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (
    SHAPES, ShapeConfig, all_arch_ids, get_config, model_flops, param_count,
    shape_applicable,
)
from repro.launch import mesh as mesh_mod
from repro.launch.hlo_analysis import analyze
from repro.models import model as lm
from repro.optim import AdamWConfig, init as opt_init, state_specs, update as opt_update, warmup_cosine
from repro.parallel.sharding import ParallelContext, param_specs

ART_DIR = os.path.join(os.path.dirname(__file__), "../../../experiments/dryrun")


def _ns(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, sp), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _abstract(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree
    )


def build_cell(arch: str, shape_name: str, multi_pod: bool, *, sp: bool = False,
               ep_shardmap: bool = False, decode_opt: bool = False,
               decode_unroll: int = 1, chunk: int = 512, microbatch: int = 1):
    """Returns (jitted fn, example abstract args) for one cell."""
    cfg = get_config(arch)
    if decode_opt:
        cfg = cfg.replace(decode_mxu_einsum=True, decode_unroll=decode_unroll,
                          decode_appended_kv=True, kv_cache_layout="dot")
    shape = SHAPES[shape_name]
    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    ctx = mesh_mod.make_context(mesh, cfg, sp=sp)
    if ep_shardmap:
        ctx = ctx._replace(ep_shardmap=True)

    params_abs = lm.abstract_params(cfg, ctx)
    pspecs = param_specs(params_abs, ctx)
    params_sh = _ns(mesh, pspecs)
    batch_abs = lm.input_specs(cfg, shape)
    bspecs = lm.batch_specs(cfg, shape, ctx)
    batch_sh = _ns(mesh, bspecs)

    if shape.kind == "train":
        opt_cfg = AdamWConfig(state_dtype="bfloat16" if cfg.fsdp else "float32")
        opt_abs = jax.eval_shape(partial(opt_init, cfg=opt_cfg), params_abs)
        ospecs = state_specs(pspecs, params_abs, ctx)
        opt_sh = _ns(mesh, ospecs)

        def train_step(params, opt, batch):
            lr = warmup_cosine(opt.step)
            if microbatch > 1:
                # gradient accumulation: halves live activation memory at
                # identical math (loss/grads averaged over microbatches)
                mb = jax.tree_util.tree_map(
                    lambda x: x.reshape((microbatch, x.shape[0] // microbatch)
                                        + x.shape[1:]), batch)

                def body(acc, b):
                    (l, m), g = jax.value_and_grad(lm.loss_fn, has_aux=True)(
                        params, b, cfg, ctx, chunk=chunk)
                    acc = jax.tree_util.tree_map(jnp.add, acc, g)
                    return acc, l

                zeros = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                gsum, losses = jax.lax.scan(body, zeros, mb)
                grads = jax.tree_util.tree_map(lambda g: g / microbatch, gsum)
                loss, metrics = jnp.mean(losses), {}
            else:
                (loss, metrics), grads = jax.value_and_grad(
                    lm.loss_fn, has_aux=True
                )(params, batch, cfg, ctx, chunk=chunk)
            grads = lm.postprocess_grads(grads, cfg, ctx)
            params, opt, om = opt_update(grads, opt, params, lr, opt_cfg)
            return params, opt, {"loss": loss, **metrics, **om}

        fn = jax.jit(
            train_step,
            in_shardings=(params_sh, opt_sh, batch_sh),
            out_shardings=(params_sh, opt_sh, None),
            donate_argnums=(0, 1),
        )
        args = (params_abs, opt_abs, batch_abs)
        return fn, args, cfg, shape, mesh, ctx

    if shape.kind == "prefill":
        state_abs = jax.eval_shape(
            lambda: lm.make_decode_state(cfg, ctx, shape.global_batch, shape.seq_len)
        )
        sspecs = lm.decode_state_specs(cfg, ctx, shape.global_batch)
        state_sh = _ns(mesh, sspecs)

        def prefill_step(params, batch, state):
            return lm.prefill(
                params, batch["tokens"], state, cfg, ctx,
                media=batch.get("media"), chunk=chunk,
            )

        fn = jax.jit(
            prefill_step,
            in_shardings=(params_sh, batch_sh, state_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(2,),
        )
        args = (params_abs, batch_abs, state_abs)
        return fn, args, cfg, shape, mesh, ctx

    # decode: one token against a cache of seq_len
    state_abs = jax.eval_shape(
        lambda: lm.make_decode_state(cfg, ctx, shape.global_batch, shape.seq_len)
    )
    # cache is "full": pos = seq_len (the new token overwrites ring slot)
    sspecs = lm.decode_state_specs(cfg, ctx, shape.global_batch)
    state_sh = _ns(mesh, sspecs)

    def serve_step(params, batch, state):
        return lm.decode_step(params, batch["tokens"], state, cfg, ctx)

    fn = jax.jit(
        serve_step,
        in_shardings=(params_sh, batch_sh, state_sh),
        out_shardings=(state_sh, None),
        donate_argnums=(2,),
    )
    args = (params_abs, batch_abs, state_abs)
    return fn, args, cfg, shape, mesh, ctx


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str = ART_DIR,
             tag: str = "", **build_kw) -> dict:
    mesh_name = "multi" if multi_pod else "single"
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    cell_id = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    if not shape_applicable(cfg, shape):
        rec = {
            "cell": cell_id, "skipped": True,
            "reason": "long_500k requires sub-quadratic sequence mixing "
                      "(full-attention arch; see DESIGN.md #Arch-applicability)",
        }
        _write(out_dir, cell_id, rec)
        return rec

    t0 = time.time()
    fn, args, cfg, shape, mesh, ctx = build_cell(arch, shape_name, multi_pod, **build_kw)
    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    n_dev = mesh.devices.size
    pod_size = 256
    # loop-aware HLO cost model (XLA's own cost_analysis counts while-loop
    # bodies once — see hlo_analysis.py): flops/bytes/collectives per device
    coll = analyze(hlo, pod_size=pod_size)

    flops_dev = float(coll.flops)
    bytes_dev = float(coll.bytes)
    mf = model_flops(cfg, shape)
    mem_rec = {
        k: int(getattr(mem, k, 0) or 0)
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes")
    }
    args_b = mem_rec["argument_size_in_bytes"]
    temp_b = mem_rec["temp_size_in_bytes"]

    compute_term = flops_dev / mesh_mod.PEAK_FLOPS_BF16
    memory_term = bytes_dev / mesh_mod.HBM_BW
    # TPU-projected memory term: pure data-movement (bf16<->f32 legalization,
    # layout copies) excluded — the CPU backend materializes these, a TPU
    # compile does not (native bf16, fused layout changes)
    memory_term_tpu = coll.compute_bytes / mesh_mod.HBM_BW
    ici_term = coll.ici_bytes / mesh_mod.ICI_BW
    dcn_term = coll.dcn_bytes / mesh_mod.DCN_BW
    coll_term = ici_term + dcn_term
    terms = {"compute_s": compute_term, "memory_s": memory_term,
             "memory_tpu_s": memory_term_tpu,
             "collective_s": coll_term, "ici_s": ici_term, "dcn_s": dcn_term}
    dominant = max(
        ("compute_s", "memory_tpu_s", "collective_s"), key=lambda k: terms[k]
    )

    rec = {
        "cell": cell_id,
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "devices": int(n_dev),
        "skipped": False,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "params": param_count(cfg),
        "model_flops_step": mf,
        "hlo_flops_per_dev": flops_dev,
        "hlo_bytes_per_dev": bytes_dev,
        "xla_cost_analysis_flops": float(xla_cost.get("flops", 0.0)),
        "collectives": coll.to_json(),
        "memory_analysis": mem_rec,
        "fits_hbm": bool((args_b + temp_b) < mesh_mod.HBM_BYTES),
        "terms_s": terms,
        "dominant": dominant,
        "useful_flops_ratio": (mf / max(n_dev, 1)) / max(flops_dev, 1.0),
        "step_time_bound_s": max(terms["compute_s"], terms["memory_tpu_s"], terms["collective_s"]),
        "roofline_fraction": compute_term / max(
            compute_term, memory_term_tpu, coll_term
        ),
    }
    _write(out_dir, cell_id, rec)
    return rec


def _write(out_dir, cell_id, rec):
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, cell_id + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    # 512 host devices stand in for the production meshes; set here, not at
    # import, so importing this module never changes a process's devices
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", "")
    )
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=ART_DIR)
    ap.add_argument("--sp", action="store_true", help="sequence sharding")
    ap.add_argument("--ep-shardmap", action="store_true")
    ap.add_argument("--decode-opt", action="store_true")
    ap.add_argument("--decode-unroll", type=int, default=1)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--tag", default="")
    ap.add_argument("--chunk", type=int, default=512)
    args = ap.parse_args()

    cells = []
    archs = all_arch_ids() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for a in archs:
        for s in shapes:
            for m in meshes:
                cells.append((a, s, m == "multi"))

    failures = 0
    for a, s, mp in cells:
        try:
            rec = run_cell(a, s, mp, out_dir=args.out, tag=args.tag,
                           sp=args.sp, ep_shardmap=args.ep_shardmap,
                           decode_opt=args.decode_opt,
                           decode_unroll=args.decode_unroll,
                           microbatch=args.microbatch, chunk=args.chunk)
            if rec.get("skipped"):
                print(f"[SKIP] {rec['cell']}: {rec['reason'][:60]}")
            else:
                t = rec["terms_s"]
                print(
                    f"[OK]   {rec['cell']}: compile={rec['compile_s']}s "
                    f"args={rec['memory_analysis']['argument_size_in_bytes']/2**30:.2f}GiB "
                    f"temp={rec['memory_analysis']['temp_size_in_bytes']/2**30:.2f}GiB "
                    f"terms(c/m/n)={t['compute_s']:.3f}/{t['memory_s']:.3f}/"
                    f"{t['collective_s']:.3f}s dom={rec['dominant']}"
                )
        except Exception as e:
            failures += 1
            print(f"[FAIL] {a}__{s}__{'multi' if mp else 'single'}: {type(e).__name__}: {e}")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
