import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the
# device count on first backend initialization).

# Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.
#
# For each cell this proves the distribution config is coherent on the
# production mesh (sharding propagation, collective legality, per-chip
# memory) and extracts the roofline terms — the platform's static
# resource-estimation stage (paper C2) applied to TPU pods.
#
# Usage:
#   python -m repro.launch.dryrun --arch internlm2-1.8b --shape train_4k
#   python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun

import argparse
import json
import time
import traceback
from pathlib import Path
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro import configs, flags as perf_flags
from repro.core.arch import SHAPES, ArchConfig, ShapeConfig, shape_applicable
from repro.launch.mesh import make_production_mesh, mesh_name
from repro.models import api
from repro.models.params import abstract_params, logical_axes, param_count
from repro.roofline.collect import analyze_module, total_collective_bytes
from repro.roofline.model import (RooflineReport, fused_adjustment,
                                  model_flops)
from repro.sharding.policy import (AxisRules, logical_to_pspec, make_rules,
                                   params_pspecs)
from repro.serve.serve_step import make_decode_step, make_prefill_step
from repro.train.optimizer import abstract_opt_state
from repro.train.train_step import make_train_step

from jax.sharding import NamedSharding, PartitionSpec as P

# Archs whose q-head count does not divide the 16-way model axis use
# context-parallel attention; archs whose train activations overflow a
# 16 GiB chip under plain TP default to Megatron-SP (measured: qwen2
# 24.0→9.6 GiB, dbrx 16.1→12.6 GiB; see EXPERIMENTS.md §Perf).
DEFAULT_STRATEGY = {
    "gemma3-4b": "cp",       # 8 q heads
    "llama3.2-3b": "cp",     # 24 q heads
    "qwen2-vl-72b": "tp_sp",
    "dbrx-132b": "tp_sp",
}


def default_strategy(arch: str) -> str:
    return DEFAULT_STRATEGY.get(arch, "tp")


def default_n_micro(cfg: ArchConfig, shape: ShapeConfig, mesh) -> int:
    if shape.kind != "train":
        return 1
    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    per_dp = 1 if param_count(cfg) > 2e10 else 2
    n = max(shape.global_batch // (dp * per_dp), 1)
    while shape.global_batch % n:
        n -= 1
    return n


# ---------------------------------------------------------------------------
# Sharding of inputs
# ---------------------------------------------------------------------------
def _batch_shardings(cfg, shape, mesh, rules, specs):
    axes = api.input_logical_axes(cfg, shape)
    return {
        name: NamedSharding(mesh, logical_to_pspec(
            axes[name], rules, mesh, specs[name].shape))
        for name in specs
    }


def cache_shardings(cfg, cache, mesh, rules):
    def assign(path, leaf):
        key = "/".join(str(getattr(p, "key", p)) for p in path).lower()
        nd = len(leaf.shape)
        if "pos" in key:
            axes = (None,) * (nd - 2) + ("act_batch", "act_cache_seq")
        elif "conv" in key:
            axes = (None,) * (nd - 3) + ("act_batch", None, "act_dinner")
        elif "ssm" in key:
            if nd >= 4:  # (..., B, di|nh, ds|P, [ds])
                tail = (("act_batch", "act_dinner", None, None) if nd >= 4
                        else ("act_batch", "act_dinner", None))
                tail = tail[:min(4, nd)]
                axes = (None,) * (nd - len(tail)) + tail
            else:
                axes = (None,) * nd
        else:
            axes = (None,) * (nd - 4) + ("act_batch", "act_cache_seq",
                                         "act_kv_heads", None)
        return NamedSharding(mesh, logical_to_pspec(axes, rules, mesh,
                                                    leaf.shape))
    return jax.tree_util.tree_map_with_path(assign, cache)


# ---------------------------------------------------------------------------
# Per-cell dry run
# ---------------------------------------------------------------------------
def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             strategy: Optional[str] = None, n_micro: Optional[int] = None,
             remat: str = "full", save_hlo: Optional[Path] = None,
             grad_compression: Optional[str] = None,
             opt_flags: Optional[Dict[str, bool]] = None) -> Dict[str, Any]:
    if opt_flags:
        perf_flags.set_flags(**opt_flags)
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped", "why": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    strategy = strategy or default_strategy(arch)
    rules = make_rules(strategy, multi_pod=multi_pod,
                       decode=shape.kind == "decode")
    n_micro = n_micro or default_n_micro(cfg, shape, mesh)

    t0 = time.time()
    aparams = abstract_params(cfg)
    plax = logical_axes(cfg)
    param_sh = params_pspecs(plax, rules, mesh, aparams)

    result: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name(mesh),
        "strategy": strategy, "n_micro": n_micro, "remat": remat,
        "n_chips": mesh.size, "params": param_count(cfg),
        "flags": dict(perf_flags.FLAGS),
    }

    if shape.kind == "train":
        specs = api.input_specs(cfg, shape)
        batch_sh = _batch_shardings(cfg, shape, mesh, rules, specs)
        aopt = abstract_opt_state(aparams)
        opt_sh = {"m": param_sh, "v": param_sh,
                  "step": NamedSharding(mesh, P())}
        step = make_train_step(cfg, n_microbatch=n_micro, remat=remat,
                               rules=rules, mesh=mesh,
                               grad_compression=grad_compression)
        jstep = jax.jit(step, in_shardings=(param_sh, opt_sh, batch_sh),
                        donate_argnums=(0, 1))
        lowered = jstep.lower(aparams, aopt, specs)
    elif shape.kind == "prefill":
        specs = api.input_specs(cfg, shape)
        batch_sh = _batch_shardings(cfg, shape, mesh, rules, specs)
        step = make_prefill_step(cfg, rules=rules, mesh=mesh)
        jstep = jax.jit(step, in_shardings=(param_sh, batch_sh))
        lowered = jstep.lower(aparams, specs)
    else:  # decode
        specs = api.input_specs(cfg, shape)
        cache_sh = cache_shardings(cfg, specs["cache"], mesh, rules)
        tok_sh = NamedSharding(mesh, logical_to_pspec(
            ("act_batch",), rules, mesh, specs["token"].shape))
        step = make_decode_step(cfg, rules=rules, mesh=mesh)
        jstep = jax.jit(step, in_shardings=(param_sh, cache_sh, tok_sh,
                                            tok_sh),
                        donate_argnums=(1,))
        lowered = jstep.lower(aparams, specs["cache"], specs["token"],
                              specs["position"])

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    wc = analyze_module(hlo)   # loop-weighted (cost_analysis is not)
    colls = {k: dict(v) for k, v in wc.collectives.items()}
    if save_hlo:
        save_hlo.parent.mkdir(parents=True, exist_ok=True)
        save_hlo.write_text(hlo)

    per_dev_hbm = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                   + mem.output_size_in_bytes - mem.alias_size_in_bytes
                   + mem.generated_code_size_in_bytes)
    rep = RooflineReport(
        arch=arch, shape=shape_name, mesh=result["mesh"],
        n_chips=mesh.size,
        hlo_flops=wc.flops,
        hlo_bytes=wc.bytes_accessed,
        hlo_bytes_min=wc.bytes_min,
        collective_bytes=total_collective_bytes(colls),
        collective_detail=colls,
        per_device_hbm=float(per_dev_hbm),
        model_flops=model_flops(cfg, shape),
    ).finalize()

    result.update({
        "status": "ok",
        "t_lower_s": round(t_lower, 2), "t_compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "per_device_hbm_bytes": per_dev_hbm,
            "per_device_hbm_gib": round(per_dev_hbm / 2**30, 3),
        },
        "cost": {"flops_per_device": rep.hlo_flops,
                 "bytes_per_device": rep.hlo_bytes,
                 "xla_cost_analysis_flops_unweighted":
                     float(cost.get("flops", 0.0))},
        "collectives": colls,
        "roofline": {**rep.row(), **fused_adjustment(cfg, shape, rep)},
        "model_flops": rep.model_flops,
    })
    return result


def print_summary(res: Dict[str, Any]) -> None:
    if res.get("status") == "skipped":
        print(f"[skip] {res['arch']} x {res['shape']} x {res['mesh']}: "
              f"{res['why']}")
        return
    r = res["roofline"]
    print(f"[ok]   {res['arch']} x {res['shape']} x {res['mesh']} "
          f"strat={res['strategy']} micro={res['n_micro']} "
          f"lower={res['t_lower_s']}s compile={res['t_compile_s']}s")
    print(f"       hbm/dev={res['memory']['per_device_hbm_gib']} GiB "
          f"fits={r['fits_hbm']}  bottleneck={r['bottleneck']}")
    print(f"       t_comp={r['t_compute_s']}s t_mem={r['t_memory_s']}s "
          f"t_coll={r['t_collective_s']}s useful={r['useful_flops_ratio']} "
          f"roofline_frac={r['roofline_fraction']}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--strategy", default=None)
    ap.add_argument("--micro", type=int, default=None)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells whose JSON already exists")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--grad-compression", default=None)
    ap.add_argument("--opt", action="store_true",
                    help="enable beyond-paper perf flags "
                         "(bf16_params + bf16_attn_p)")
    args = ap.parse_args()
    if args.opt:
        perf_flags.set_flags(bf16_params=True, bf16_attn_p=True)

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    archs = list(configs.ALIASES) if (args.all or not args.arch) \
        else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape_name in shapes:
            for multi in meshes:
                tag = f"{arch}_{shape_name}_{'multi' if multi else 'single'}"
                path = out / f"{tag}.json"
                if args.resume and path.exists():
                    print(f"[resume] {tag} exists")
                    continue
                try:
                    res = run_cell(
                        arch, shape_name, multi_pod=multi,
                        strategy=args.strategy, n_micro=args.micro,
                        remat=args.remat,
                        grad_compression=args.grad_compression,
                        save_hlo=(out / f"{tag}.hlo.txt"
                                  if args.save_hlo else None))
                except Exception as e:  # a failure here is a bug — record it
                    res = {"arch": arch, "shape": shape_name,
                           "mesh": "2x16x16" if multi else "16x16",
                           "status": "error", "error": str(e)[:2000],
                           "traceback": traceback.format_exc()[-4000:]}
                    failures.append(tag)
                path.write_text(json.dumps(res, indent=1))
                if res["status"] == "error":
                    print(f"[FAIL] {tag}: {res['error'][:200]}")
                else:
                    print_summary(res)
    if failures:
        print(f"\n{len(failures)} FAILURES: {failures}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
