import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import: jax locks the device
# count at first init. DRYRUN_DEVICES overrides for the tiny test mesh.
if os.environ.get("DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell on placeholder devices; record memory_analysis, cost_analysis and
loop-aware roofline terms. No real allocation happens — inputs are
ShapeDtypeStructs.

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  python -m repro.launch.dryrun --arch qwen2-7b --shape decode_32k --multipod
  python -m repro.launch.dryrun --all --out experiments/dryrun
"""

import argparse
import json
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import ARCH_IDS, SHAPES, get_config, get_smoke_config, \
    shape_applicable
from ..distributed.sharding import (
    Runtime, batch_specs, cache_specs, make_param_shardings,
    normalize_shardings)
from ..launch.mesh import batch_axes, make_production_mesh, make_test_mesh
from ..launch.specs import input_specs
from ..launch.steps import make_prefill_step, make_serve_step, \
    make_train_step
from ..launch import roofline
from ..models import lm
from ..optim import adamw
from jax.sharding import NamedSharding, PartitionSpec as P


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             mesh_kind: str = "prod", smoke: bool = False,
             remat: str = "full", moe_impl: str = "shard_map",
             save_hlo: str = "", seq_parallel: bool = False,
             bf16_gather: bool = False, moe_ep: str = None,
             serve_stationary: bool = False, loss_chunk: int = 0) -> dict:
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    if not moe_ep:
        moe_ep = getattr(cfg, "moe_ep_pref", "data")
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name,
           "mesh": ("multipod" if multi_pod else "singlepod"),
           "mesh_kind": mesh_kind, "kind": shape.kind}
    if not shape_applicable(cfg, shape):
        rec["status"] = "skipped"
        rec["reason"] = ("long_500k requires sub-quadratic attention; "
                        "skipped for pure full-attention archs "
                        "(DESIGN.md §Arch-applicability)")
        return rec

    mesh = (make_production_mesh(multi_pod=multi_pod) if mesh_kind == "prod"
            else make_test_mesh(multi_pod=multi_pod))
    n_dev = mesh.size
    long_ctx = shape_name == "long_500k"
    rt = Runtime(mesh=mesh, batch_axes=batch_axes(mesh), remat=remat,
                 moe_impl=moe_impl, seq_shard_decode=long_ctx,
                 seq_parallel=seq_parallel, bf16_gather=bf16_gather,
                 moe_ep=moe_ep, loss_chunk=loss_chunk)

    t0 = time.time()
    params_shape = jax.eval_shape(
        lambda k: lm.init_params(k, cfg, rt), jax.random.PRNGKey(0))
    if serve_stationary and shape.kind != "train":
        # weight-stationary serving: bf16 weights sharded over TP only
        params_shape = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, jnp.bfloat16 if x.dtype == jnp.float32 else x.dtype),
            params_shape)
        p_sh = make_param_shardings(mesh, params_shape, fsdp=None,
                                    moe_ep=moe_ep)
    else:
        p_sh = make_param_shardings(mesh, params_shape, moe_ep=moe_ep)
    batch = input_specs(cfg, shape)
    b_sh = normalize_shardings(
        mesh, batch_specs(shape.kind, cfg, rt),
        {k: batch[k] for k in batch})

    if shape.kind == "train":
        opt_cfg = adamw.AdamWConfig()
        opt_shape = jax.eval_shape(
            lambda p: adamw.init_state(p, opt_cfg), params_shape)
        o_sh = {"mu": p_sh, "nu": p_sh,
                "step": NamedSharding(mesh, P())}
        step = make_train_step(cfg, rt, opt_cfg)
        jf = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, None),
                     donate_argnums=(0, 1))
        lowered = jf.lower(params_shape, opt_shape, batch)
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, rt)
        jf = jax.jit(step, in_shardings=(p_sh, b_sh))
        lowered = jf.lower(params_shape, batch)
    else:  # decode
        cache_shape = jax.eval_shape(
            lambda: lm.init_cache(cfg, shape.global_batch, shape.seq_len,
                                  rt))
        c_sh = normalize_shardings(
            mesh, cache_specs(cfg, rt, long_context=long_ctx), cache_shape)
        step = make_serve_step(cfg, rt)
        jf = jax.jit(step, in_shardings=(p_sh, c_sh, b_sh),
                     out_shardings=(None, c_sh), donate_argnums=(1,))
        lowered = jf.lower(params_shape, cache_shape, batch)
    t_lower = time.time() - t0

    t1 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t1

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    costs = roofline.analyze(hlo, n_dev)
    terms = roofline.roofline_terms(costs, roofline.DRYRUN_DEVICE_KIND)
    mflops = roofline.model_flops(cfg, shape)

    rec.update({
        "status": "ok",
        "n_devices": n_dev,
        "t_lower_s": round(t_lower, 2),
        "t_compile_s": round(t_compile, 2),
        "params": int(cfg.param_count()),
        "active_params": int(cfg.active_param_count()),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            # XLA:CPU hoists a bf16->f32 convert of the remat-saved layer
            # inputs out of the backward loop, materializing an extra f32
            # stacked buffer that a TPU build does not allocate. Subtract it
            # to estimate the TPU-side temp footprint (see EXPERIMENTS.md).
            "cpu_f32_remat_artifact_bytes": _remat_artifact(cfg, shape, rt),
        },
        "cost_analysis": {"flops_per_dev_iter": ca.get("flops"),
                          "bytes_accessed": ca.get("bytes accessed")},
        "hlo_loop_aware": {
            "flops_per_dev": costs.flops,
            "hbm_bytes_per_dev": costs.hbm_bytes,
            "ici_bytes_per_dev": costs.ici_bytes,
            "collectives": costs.collective_counts,
            "unknown_while": costs.unknown_while,
        },
        "roofline": terms,
        "model_flops_global": mflops,
        "model_flops_per_dev": mflops / n_dev,
        "useful_flops_ratio": (mflops / n_dev) / costs.flops
        if costs.flops else None,
    })
    return rec


def _remat_artifact(cfg, shape, rt) -> int:
    if shape.kind != "train" or rt.remat == "none":
        return 0
    ndev_batch = 1
    for ax in rt.batch_axes:
        ndev_batch *= rt.mesh.shape[ax]
    b_loc = max(shape.global_batch // ndev_batch, 1)
    seq = shape.seq_len
    if rt.seq_parallel:  # the hoisted f32 copy is sequence-sharded too
        seq //= rt.mesh.shape[rt.tp_axis]
    return int(cfg.n_layers * b_loc * seq * cfg.d_model * 4)


def _cell_subprocess(arch, shape, multipod, args) -> dict:
    cmd = [sys.executable, "-m", "repro.launch.dryrun",
           "--arch", arch, "--shape", shape]
    if multipod:
        cmd.append("--multipod")
    if args.smoke:
        cmd.append("--smoke")
    if args.mesh != "prod":
        cmd += ["--mesh", args.mesh]
    if args.remat != "full":
        cmd += ["--remat", args.remat]
    if args.serve_stationary:
        cmd.append("--serve-stationary")
    if args.seq_parallel:
        cmd.append("--seq-parallel")
    if args.loss_chunk:
        cmd += ["--loss-chunk", str(args.loss_chunk)]
    out = subprocess.run(cmd, capture_output=True, text=True,
                         timeout=args.timeout)
    for line in reversed(out.stdout.splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    return {"arch": arch, "shape": shape,
            "mesh": "multipod" if multipod else "singlepod",
            "status": "error",
            "stderr": out.stderr[-4000:], "stdout": out.stdout[-2000:]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--mesh", default="prod", choices=("prod", "test"))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--remat", default="full",
                    choices=("none", "dots", "full"))
    ap.add_argument("--moe-impl", default="shard_map",
                    choices=("shard_map", "local"))
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--bf16-gather", action="store_true")
    ap.add_argument("--moe-ep", default="", choices=("", "data", "model"))
    ap.add_argument("--serve-stationary", action="store_true")
    ap.add_argument("--loss-chunk", type=int, default=0)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--save-hlo", default="")
    ap.add_argument("--timeout", type=int, default=3600)
    args = ap.parse_args()

    if args.all:
        os.makedirs(args.out or "experiments/dryrun", exist_ok=True)
        outdir = args.out or "experiments/dryrun"
        for arch in ARCH_IDS:
            for shape in SHAPES:
                for multipod in (False, True):
                    tag = f"{arch}__{shape}__" + \
                        ("multipod" if multipod else "singlepod")
                    path = os.path.join(outdir, tag + ".json")
                    if os.path.exists(path):
                        continue
                    t0 = time.time()
                    try:
                        rec = _cell_subprocess(arch, shape, multipod, args)
                    except subprocess.TimeoutExpired:
                        rec = {"arch": arch, "shape": shape,
                               "status": "timeout"}
                    rec["wall_s"] = round(time.time() - t0, 1)
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                    print(tag, rec.get("status"), f"{rec['wall_s']}s",
                          flush=True)
        return

    try:
        rec = run_cell(args.arch, args.shape, args.multipod, args.mesh,
                       args.smoke, args.remat, args.moe_impl,
                       args.save_hlo, seq_parallel=args.seq_parallel,
                       bf16_gather=args.bf16_gather,
                       moe_ep=args.moe_ep or None,
                       serve_stationary=args.serve_stationary,
                       loss_chunk=args.loss_chunk)
    except Exception as e:  # noqa
        rec = {"arch": args.arch, "shape": args.shape, "status": "error",
               "error": repr(e), "trace": traceback.format_exc()[-4000:]}
    print(json.dumps(rec))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
