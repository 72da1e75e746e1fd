import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST precede any other import (jax locks the device
count on first init). Do not move them.

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k
  python -m repro.launch.dryrun --arch granite-8b --shape decode_32k --multi-pod
  python -m repro.launch.dryrun --all --out results/dryrun

Each cell emits a JSON record: per-device memory analysis, loop-aware HLO
flops/bytes/collective-bytes (see hlo_stats), raw cost_analysis values, and
the three roofline terms.
"""
import argparse
import json
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED, SHAPES, applicable_shapes, get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed import sharding as sh
from repro.launch import specs as SP
from repro.launch.hlo_stats import analyze
from repro.launch.mesh import TPU_V5E, make_mesh, make_production_mesh
from repro.models import cache_axes, decode_step, param_axes, prefill
from repro.training.optimizer import opt_state_axes
from repro.training.train_step import make_train_step


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6·N·D (dense) or 6·N_active·D (MoE); D = tokens processed."""
    n = cfg.n_active_params
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens          # forward only
    tokens = shape.global_batch            # one token per item
    return 2.0 * n * tokens


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
               opts: Optional[Dict[str, str]] = None):
    """Returns (fn, args_sds, in_shardings, rules).

    opts — perf-iteration knobs (EXPERIMENTS.md §Perf):
      fsdp=none|data        weight sharding over the data axis
      remat_policy=none|dots  activation-checkpoint policy
      mb=<int>              gradient-accumulation microbatches
      flash_block=<int>     flash-attention block size (q and k)
      moe=dense|scatter|auto  MoE dispatch implementation
      kv_quant=1            int8 KV cache for decode shapes
    """
    opts = opts or {}
    fsdp = {"none": None, "data": "data"}.get(opts.get("fsdp", "data"),
                                              "data")
    rules = SP.rules_for(cfg, shape, mesh, fsdp=fsdp)
    if opts.get("moe_shard") == "2d":
        # 2D expert sharding: experts over data, per-expert FFN over model
        # (DeepSpeed-MoE-style EP=DP + TP inside the expert)
        rules["expert"] = "data"
        rules["ffe"] = "model"
    if "flash_block" in opts:
        from repro.models import layers as L
        L.FLASH_BLOCK = int(opts["flash_block"])
    if "moe" in opts:
        from repro.models import layers as L
        L.MOE_IMPL = opts["moe"]
    kv_quant = bool(int(opts.get("kv_quant", "0")))
    with sh.use_rules(rules, mesh):
        p_sds = SP.params_sds(cfg)
        p_shard = SP.shardings_for(param_axes(cfg), mesh)
        if shape.kind == "train":
            o_sds = SP.opt_state_sds(cfg)
            o_shard = SP.shardings_for(opt_state_axes(param_axes(cfg)), mesh)
            b_sds = SP.batch_sds(cfg, shape)
            b_shard = SP.shardings_for(SP.batch_axes(cfg, shape), mesh)
            # grad-accumulate in microbatches: 1M-token global steps do not
            # fit activations otherwise (16 leaves 1 batch row per device)
            mb = int(opts.get("mb", 16))
            mb = mb if shape.global_batch % mb == 0 else 1
            fn = make_train_step(cfg, microbatches=mb,
                                 remat_policy=opts.get("remat_policy",
                                                       "none"))
            return (fn, (p_sds, o_sds, b_sds),
                    (p_shard, o_shard, b_shard), rules)
        if shape.kind == "prefill":
            b_sds = SP.batch_sds(cfg, shape)
            b_shard = SP.shardings_for(SP.batch_axes(cfg, shape), mesh)

            def fn(params, batch):
                return prefill(params, cfg, tokens=batch.get("tokens"),
                               embeds=batch.get("embeds"))
            return fn, (p_sds, b_sds), (p_shard, b_shard), rules
        # decode
        c_sds = SP.cache_sds(cfg, shape.global_batch, shape.seq_len,
                             quant=kv_quant)
        c_shard = SP.shardings_for(cache_axes(cfg, quant=kv_quant), mesh)
        b_sds = SP.batch_sds(cfg, shape)
        b_shard = SP.shardings_for(SP.batch_axes(cfg, shape), mesh)

        def fn(params, cache, batch):
            return decode_step(params, cfg, cache,
                               tokens=batch.get("tokens"),
                               embeds=batch.get("embeds"), uniform_pos=True)
        return fn, (p_sds, c_sds, b_sds), (p_shard, c_shard, b_shard), rules


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             verbose: bool = True,
             opts: Optional[Dict[str, str]] = None) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if opts and "tp" in opts:
        # perf-iteration knob: re-balance the 256 chips between DP and TP
        tp = int(opts["tp"])
        total = 512 if multi_pod else 256
        per_pod = total // (2 if multi_pod else 1)
        if multi_pod:
            mesh = make_mesh((2, per_pod // tp, tp),
                             ("pod", "data", "model"))
        else:
            mesh = make_mesh((per_pod // tp, tp), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    fn, args_sds, in_shardings, rules = build_cell(cfg, shape, mesh, opts)

    # buffer donation: decode steps donate the cache (in-place KV update);
    # train steps donate params + optimizer state (in-place weight update)
    donate = ()
    if shape.kind == "decode":
        donate = (1,)
    elif shape.kind == "train":
        donate = (0, 1)

    t0 = time.time()
    with sh.use_rules(rules, mesh), mesh:
        lowered = jax.jit(fn, in_shardings=in_shardings,
                          donate_argnums=donate).lower(*args_sds)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = analyze(compiled.as_text())

    # roofline terms (per device; hlo stats are already per-device)
    t_compute = hlo.flops / TPU_V5E.flops
    t_memory = hlo.bytes / TPU_V5E.hbm_bw
    t_coll = hlo.coll_bytes / TPU_V5E.ici_bw
    dominant = max((("compute", t_compute), ("memory", t_memory),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]
    mf = model_flops(cfg, shape)
    rec = {
        "arch": arch, "shape": shape_name, "opts": opts or {},
        "mesh": "2x16x16" if multi_pod else "16x16",
        "peaks": TPU_V5E.name,
        "n_devices": n_dev,
        "ok": True,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "per_device_bytes": {
            "arguments": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "total": mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes,
            # XLA:CPU materializes f32 upcast copies of bf16 args in temp;
            # on TPU those don't exist. Estimate = temp - 2x(bf16 args).
            "temp_tpu_estimate": max(
                0, mem.temp_size_in_bytes - 2 * mem.argument_size_in_bytes),
        },
        "hlo_flops_per_dev": hlo.flops,
        "hlo_bytes_per_dev": hlo.bytes,
        "coll_bytes_per_dev": hlo.coll_bytes,
        "coll_counts": hlo.coll_counts,
        "raw_cost_analysis": {k: ca.get(k) for k in
                              ("flops", "bytes accessed")},
        "roofline": {
            "compute_s": t_compute,
            "memory_s": t_memory,
            "collective_s": t_coll,
            "dominant": dominant,
            "bound_s": max(t_compute, t_memory, t_coll),
        },
        "model_flops_total": mf,
        "model_flops_per_dev": mf / n_dev,
        "useful_flops_ratio": (mf / n_dev) / hlo.flops if hlo.flops else 0.0,
    }
    if verbose:
        print(json.dumps(rec, indent=2))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str)
    ap.add_argument("--shape", type=str)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", type=str, default=None)
    ap.add_argument("--opt", action="append", default=[],
                    help="perf knob key=val (repeatable)")
    ap.add_argument("--tag", type=str, default="",
                    help="suffix for output filenames")
    args = ap.parse_args()
    opts = dict(kv.split("=", 1) for kv in args.opt)

    cells = []
    if args.all:
        for arch in ASSIGNED:
            cfg = get_config(arch)
            for shape in applicable_shapes(cfg):
                cells.append((arch, shape.name))
    else:
        if not (args.arch and args.shape):
            ap.error("give --arch and --shape, or --all")
        cells = [(args.arch, args.shape)]

    n_failed = 0
    for arch, shape in cells:
        try:
            rec = run_cell(arch, shape, multi_pod=args.multi_pod,
                           verbose=not args.out, opts=opts)
        except Exception as e:  # noqa: BLE001 — record the failure
            rec = {"arch": arch, "shape": shape, "ok": False,
                   "error": f"{type(e).__name__}: {e}"}
            print(json.dumps(rec))
        n_failed += not rec["ok"]
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            tag = "mp" if args.multi_pod else "sp"
            if args.tag:
                tag += "__" + args.tag
            with open(f"{args.out}/{arch}__{shape}__{tag}.json", "w") as f:
                json.dump(rec, f, indent=2)
            print(f"[dryrun] {arch} x {shape} ({tag}) -> "
                  f"{'OK' if rec.get('ok') else 'FAIL'}")
    return 1 if n_failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
