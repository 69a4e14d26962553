import os
# 512 placeholder devices for the production mesh; the disabled pass is an
# XLA-CPU-only crasher (bf16 collective reducers carrying layout copies —
# "Invalid binary instruction opcode copy"); it never runs on TPU.
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    "--xla_disable_hlo_passes=all-reduce-promotion")

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this proves the distribution config is coherent on the
production mesh — 16×16 single-pod and 2×16×16 multi-pod — and extracts the
roofline terms from the compiled artifact:

  * ``compiled.memory_analysis()``  → fits-in-HBM proof (per device)
  * ``compiled.cost_analysis()``    → XLA's flops/bytes (loop bodies ×1)
  * ``repro.launch.hlo_analysis``   → loop-aware flops / HBM bytes /
                                      collective bytes (§Roofline source)

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--mode prism]
Artifacts: artifacts/dryrun/<mesh>/<arch>__<shape>__<mode>.json
"""
import argparse
import gc
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ASSIGNED_ARCHS, SHAPES_BY_NAME, get_config, shapes_for
from repro.configs.base import ModelConfig, ShapeSpec
from repro.core.costmodel import TPU_HBM_GB
from repro.core.exchange import ExchangeMode
from repro.launch.hlo_analysis import analysis_dict, analyze_hlo_text
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.sharding.specs import (batch_shardings, cache_shardings, make_plan,
                                  opt_state_shardings, param_shardings)
from repro.train.optimizer import adamw_init
from repro.train.train_step import build_train_step

DEFAULT_L = 16


def default_mode(cfg: ModelConfig, shape_kind: str = "prefill"
                 ) -> ExchangeMode:
    """The adaptive policy's static projection onto the baseline table:

    * xLSTM has no attention → LOCAL always (DESIGN.md §4).
    * Inference (prefill/decode) → PRISM — the paper's domain.
    * Training: PRISM while weights are replicable (small archs — the
      paper-faithful layout with zero FFN comm); above the FSDP threshold
      the position-wise layout loses to classic TP×FSDP because weight
      gather/grad-reduce traffic swamps the activation traffic PRISM saves
      (measured — EXPERIMENTS.md §Perf), so big-arch train cells run LOCAL.
    """
    if cfg.family == "ssm":
        return ExchangeMode.LOCAL
    if shape_kind == "train":
        from repro.sharding.specs import _param_gb
        if _param_gb(cfg) > 20:
            return ExchangeMode.LOCAL
    return ExchangeMode.PRISM


def model_flops(cfg: ModelConfig, shape: ShapeSpec, n_params: int) -> float:
    """Analytic MODEL_FLOPS: 6·N_active·D (train) / 2·N_active·D (fwd)."""
    active = active_params(cfg, n_params)
    tokens = (shape.global_batch * shape.seq_len
              if shape.kind in ("train", "prefill") else shape.global_batch)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * active * tokens


def active_params(cfg: ModelConfig, n_params: int) -> float:
    if not cfg.moe:
        return float(n_params)
    m = cfg.moe
    routed_per_layer = 3 * cfg.d_model * m.d_ff_expert * m.n_experts
    inactive = (3 * cfg.d_model * m.d_ff_expert * (m.n_experts - m.top_k)
                * (cfg.n_layers - m.first_dense_layers))
    return float(n_params) - inactive


def grad_accum_for(cfg: ModelConfig) -> int:
    """Microbatching keeps big-arch train cells inside 16 GB HBM: the
    per-layer residual stack scales with tokens/device ÷ accumulation."""
    from repro.sharding.specs import _param_gb
    gb = _param_gb(cfg)
    if gb > 100:
        return 16
    if gb > 20:
        return 4
    return 1


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               mode: ExchangeMode, L: int = DEFAULT_L, compile_only=True,
               grad_accum: Optional[int] = None):
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    kind = SHAPES_BY_NAME[shape_name].kind
    plan = make_plan(mesh, cfg, mode, L=L, train=kind == "train",
                     decode=kind == "decode")
    xcfg = plan.xcfg

    aparams = registry.abstract_params(cfg)
    pshard = param_shardings(plan, cfg, aparams)
    from repro.utils.tree import param_bytes, param_count
    n_params = param_count(aparams)

    with jax.sharding.set_mesh(mesh):
        if shape.kind == "train":
            from repro.sharding.specs import _param_gb
            mdt = jnp.bfloat16 if _param_gb(cfg) > 100 else jnp.float32
            aopt = jax.eval_shape(lambda p: adamw_init(p, moment_dtype=mdt),
                                  aparams)
            oshard = opt_state_shardings(plan, cfg, aopt)
            inspecs = registry.input_specs(cfg, shape)
            bshard = batch_shardings(plan, cfg, inspecs, shape.kind)
            ga = grad_accum_for(cfg) if grad_accum is None else grad_accum
            # each microbatch must still cover the batch shards
            bshards = int(np.prod([mesh.shape[a] for a in plan.batch_axes]))
            ga = max(min(ga, shape.global_batch // max(bshards, 1)), 1)
            from repro.sharding.specs import _param_gb
            import jax.numpy as _jnp
            acc_dtype = (_jnp.bfloat16 if _param_gb(cfg) > 100
                         else _jnp.float32)
            step = build_train_step(cfg, xcfg, grad_accum=ga,
                                    acc_shardings=oshard.m,
                                    acc_dtype=acc_dtype)
            fn = jax.jit(step, in_shardings=(pshard, oshard, bshard),
                         out_shardings=(pshard, oshard, None),
                         donate_argnums=(0, 1))
            lowered = fn.lower(aparams, aopt, inspecs)
        elif shape.kind == "prefill":
            inspecs = registry.input_specs(cfg, shape)
            bshard = batch_shardings(plan, cfg, inspecs, shape.kind)
            fwd = registry.prefill_fn(cfg)

            def prefill(params, batch):
                logits, aux = fwd(params, batch, xcfg)
                return logits[:, -1:], aux
            fn = jax.jit(prefill, in_shardings=(pshard, bshard))
            lowered = fn.lower(aparams, inspecs)
        else:  # decode
            inspecs = registry.input_specs(cfg, shape)
            bshard = batch_shardings(plan, cfg, inspecs, shape.kind)
            acache = registry.abstract_cache(cfg, shape, xcfg)
            cshard = cache_shardings(plan, cfg, acache)
            dec = registry.decode_fn(cfg)

            def serve_step(params, batch, cache, idx):
                return dec(params, batch, cache, idx, xcfg)
            fn = jax.jit(serve_step,
                         in_shardings=(pshard, bshard, cshard, None),
                         out_shardings=None, donate_argnums=(2,))
            lowered = fn.lower(aparams, inspecs, acache,
                               jax.ShapeDtypeStruct((), jnp.int32))
    return lowered, dict(cfg=cfg, shape=shape, n_chips=n_chips,
                         n_params=n_params,
                         param_bytes=param_bytes(aparams), plan=plan)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             mode: ExchangeMode, L: int = DEFAULT_L, out_dir="artifacts/dryrun",
             verbose=True):
    t0 = time.time()
    lowered, meta = lower_cell(arch, shape_name, multi_pod=multi_pod,
                               mode=mode, L=L)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo_cost = analyze_hlo_text(compiled.as_text())
    roof = analysis_dict(hlo_cost, meta["n_chips"])
    mf = model_flops(meta["cfg"], meta["shape"], meta["n_params"])

    per_dev_hbm = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                   - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    record = {
        "arch": arch, "shape": shape_name, "mode": mode.value, "L": L,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": meta["n_chips"],
        "n_params": meta["n_params"],
        "param_bytes": meta["param_bytes"],
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
            "per_device_total_bytes": per_dev_hbm,
            "fits_16gb": per_dev_hbm < TPU_HBM_GB * 1e9,
        },
        "xla_cost_analysis": {k: cost.get(k) for k in
                              ("flops", "bytes accessed")},
        "roofline": roof,
        "model_flops_global": mf,
        "model_flops_per_device": mf / meta["n_chips"],
        "useful_flops_ratio": (mf / meta["n_chips"]) / max(roof["per_device_flops"], 1.0),
    }
    if verbose:
        print(f"[{record['mesh']}] {arch} × {shape_name} × {mode.value}: "
              f"compile {t_compile:.0f}s, "
              f"mem/dev {per_dev_hbm/1e9:.2f} GB "
              f"(fits={record['memory']['fits_16gb']}), "
              f"flops/dev {roof['per_device_flops']:.3e}, "
              f"coll wire {roof['per_device_collective_wire_bytes']:.3e} B")
        print(f"    terms: compute {roof['compute_s']*1e3:.2f} ms | memory "
              f"{roof['memory_s']*1e3:.2f} ms | collective "
              f"{roof['collective_s']*1e3:.2f} ms")
    d = os.path.join(out_dir, record["mesh"])
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, f"{arch}__{shape_name}__{mode.value}.json"),
              "w") as f:
        json.dump(record, f, indent=1)
    return record


def all_cells():
    for arch in ASSIGNED_ARCHS:
        for shape in shapes_for(arch):
            yield arch, shape.name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mode", default=None,
                    choices=["prism", "voltage", "local"])
    ap.add_argument("--L", type=int, default=DEFAULT_L)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    cells = (list(all_cells()) if args.all
             else [(args.arch, args.shape)])
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch, shape in cells:
        cfg = get_config(arch)
        mode = (ExchangeMode(args.mode) if args.mode
                else default_mode(cfg, SHAPES_BY_NAME[shape].kind))
        for mp in meshes:
            try:
                run_cell(arch, shape, multi_pod=mp, mode=mode, L=args.L,
                         out_dir=args.out)
            except Exception as e:
                failures.append((arch, shape, mp, repr(e)))
                print(f"FAILED [{'2x16x16' if mp else '16x16'}] {arch} × "
                      f"{shape}: {e}")
                traceback.print_exc()
            gc.collect()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nALL DRY-RUN CELLS PASSED")


if __name__ == "__main__":
    main()
