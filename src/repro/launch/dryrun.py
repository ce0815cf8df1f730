import os
if __name__ == "__main__":
    # A CPU compile tool: 512 fake host devices stand in for the pods, and
    # no accelerator is opened. These MUST be set before jax initializes
    # its backend (it locks the device count on first use).
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

_DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the right step function (train_step for train_*,
prefill/decode for serve shapes) against ShapeDtypeStruct inputs — no
allocation anywhere — compiles it for the production mesh, and records:
  * memory_analysis()  (does it fit),
  * cost_analysis()    (FLOPs / bytes for the roofline),
  * the partitioned HLO's collective payloads (wire bytes),
  * the three roofline terms + dominant bottleneck (utils.roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b \
      --shape train_4k --mesh both --out experiments/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all --out experiments/dryrun
"""

import argparse
import json
import time
import traceback
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.configs.base import ModelConfig, ShapeConfig
from repro.dist.sharding import ShardingRules, make_rules
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.serve.kvcache import cache_shardings, cache_specs
from repro.serve.serve_step import make_decode_step, make_prefill_step
from repro.train.optimizer import OptimizerConfig, adamw_init
from repro.train.train_step import TrainState, make_train_step
from repro.utils import roofline

# The chip whose published peaks turn this CPU compile's FLOP, byte and
# wire counts into roofline terms. The compile itself runs on fake host
# devices; no term here is a measured time.
TARGET_KIND = "TPU v5 lite"


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def batch_specs(cfg: ModelConfig, shape: ShapeConfig, rules: ShardingRules
                ) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input (weak-type-correct,
    shardable, no device allocation)."""
    mesh = rules.mesh
    g, t = shape.global_batch, shape.seq_len
    bspec = NamedSharding(mesh, rules.batch_spec(shape.kind, g, t))
    if shape.kind == "decode":
        tok_shape = (g, 1, cfg.num_codebooks) if cfg.family == "audio" \
            else (g, 1)
        return {"tokens": _sds(tok_shape, jnp.int32, bspec)}
    if cfg.family == "audio":
        return {"tokens": _sds((g, t, cfg.num_codebooks), jnp.int32, bspec)}
    if cfg.family == "vlm":
        t_text = t - cfg.num_patches
        pspec = NamedSharding(mesh, rules.batch_spec(shape.kind, g))
        return {"tokens": _sds((g, t_text), jnp.int32, bspec),
                "patches": _sds((g, cfg.num_patches, cfg.d_model),
                                jnp.bfloat16, pspec)}
    return {"tokens": _sds((g, t), jnp.int32, bspec)}


def _params_specs(model, rules: ShardingRules):
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    shardings = rules.params_shardings(shapes, model.cfg)
    return jax.tree.map(lambda s, sh: _sds(s.shape, s.dtype, sh),
                        shapes, shardings), shardings


def _model_flops(model, shape: ShapeConfig) -> float:
    """6*N_active*D (train), 2*N_active*D (prefill), 2*N_active*B (decode)."""
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    n_active = model.active_param_count(shapes)
    emb = shapes["embed"].size
    n_eff = n_active - emb if not model.cfg.tie_embeddings else n_active
    if shape.kind == "train":
        return 6.0 * n_eff * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n_eff * shape.tokens
    return 2.0 * n_eff * shape.global_batch       # decode: 1 token / request


def depth_variant(cfg: ModelConfig, L: int) -> ModelConfig:
    """Same widths/segment structure, reduced depth (cost is affine in L).

    cost_analysis does not multiply while-loop bodies by trip count, so the
    scanned-layer cost of the full model is recovered by compiling two depth
    variants and extrapolating linearly — the fixed segments (first-dense,
    global-attention layers) are held constant so the slope is exactly the
    per-scanned-layer cost. The full-depth compile still provides
    memory_analysis (fit) and the collective schedule.
    """
    return cfg.with_depth(L).scaled(unroll=True)


def variant_depths(cfg: ModelConfig) -> Tuple[int, int]:
    n_fixed = cfg.first_dense_layers + len(cfg.global_layers)
    la = max(4, n_fixed + 4)
    return la, la + 4


def lower_cell(arch: str, shape_name: str, mesh: Mesh, *,
               grad_sync: str = "auto",
               act_constraints: bool = True,
               cfg: Optional[ModelConfig] = None) -> Tuple[Any, Any, Dict]:
    """Returns (lowered, compiled, info) for one cell."""
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        raise ValueError(f"cell skipped: {why}")
    seq_shard = shape.kind != "train" and shape.global_batch < 16
    rules = make_rules(mesh, seq_shard=seq_shard)
    # int8 grad sync runs the step inside shard_map over the dp axes: any
    # with_sharding_constraint inside may then only name the model axis.
    model_rules = rules
    if grad_sync == "int8":
        import dataclasses as _dc
        model_rules = _dc.replace(rules, dp_axes=())
    model = build_model(cfg, rules=model_rules if act_constraints else None)
    bspecs = batch_specs(cfg, shape, rules)
    pspecs, pshard = _params_specs(model, rules)

    with mesh:
        if shape.kind == "train":
            ocfg = OptimizerConfig()
            step = make_train_step(model, ocfg, mesh=mesh,
                                   dp_axes=rules.dp_axes,
                                   grad_sync=grad_sync)
            opt_specs = {
                "m": jax.tree.map(lambda s: s, pspecs),
                "v": jax.tree.map(lambda s: s, pspecs),
                "step": _sds((), jnp.int32, NamedSharding(mesh, P())),
            }
            ef = None
            if grad_sync == "int8":
                n = sum(int(p.size) for p in jax.tree.leaves(pspecs))
                ef = _sds((n,), jnp.float32, NamedSharding(mesh, P()))
            state = TrainState(params=pspecs, opt=opt_specs, ef=ef)
            lowered = jax.jit(step).lower(state, bspecs)
        elif shape.kind == "prefill":
            fn = make_prefill_step(model, shape.seq_len)
            lowered = jax.jit(fn).lower(pspecs, bspecs)
        else:  # decode
            fn = model.decode_step
            cshapes = cache_specs(model, shape.global_batch, shape.seq_len)
            cshard = cache_shardings(model, shape.global_batch, shape.seq_len,
                                     rules)
            cspecs = jax.tree.map(lambda s, sh: _sds(s.shape, s.dtype, sh),
                                  cshapes, cshard)
            clen = _sds((), jnp.int32, NamedSharding(mesh, P()))
            lowered = jax.jit(fn, donate_argnums=(2,)).lower(
                pspecs, bspecs["tokens"], cspecs, clen)
        t0 = time.perf_counter()
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0

    info = {"arch": arch, "shape": shape_name, "compile_s": compile_s,
            "chips": mesh.devices.size,
            "mesh": "x".join(str(s) for s in mesh.devices.shape)}
    return lowered, compiled, info


def _mem_dict(compiled) -> Tuple[Dict, Optional[int]]:
    try:
        mem = compiled.memory_analysis()
    except Exception:
        mem = None
    memdict: Dict[str, int] = {}
    peak = None
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                memdict[k] = int(v)
        peak = sum(memdict.get(k, 0) for k in ("argument_size_in_bytes",
                                               "output_size_in_bytes",
                                               "temp_size_in_bytes"))
        if "alias_size_in_bytes" in memdict:
            peak -= memdict["alias_size_in_bytes"]
    return memdict, peak


def _cell_costs(compiled) -> Dict[str, float]:
    cost = compiled.cost_analysis() or {}
    stats = roofline.parse_collectives(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "wire": float(stats.wire_bytes),
            "coll_by_kind": dict(stats.bytes_by_kind)}


def extrapolated_costs(arch: str, shape_name: str, mesh: Mesh, *,
                       grad_sync: str = "auto",
                       cfg_overrides: Optional[Dict] = None
                       ) -> Dict[str, float]:
    """Affine-in-depth extrapolation of cost_analysis to full depth."""
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = cfg.scaled(**cfg_overrides)
    la, lb = variant_depths(cfg)
    costs = {}
    for L in (la, lb):
        _, compiled, _ = lower_cell(arch, shape_name, mesh,
                                    grad_sync=grad_sync,
                                    cfg=depth_variant(cfg, L))
        costs[L] = _cell_costs(compiled)
        del compiled
    lf = cfg.num_layers
    out: Dict[str, Any] = {"variant_depths": [la, lb]}
    for key in ("flops", "bytes", "wire"):
        slope = (costs[lb][key] - costs[la][key]) / (lb - la)
        out[key] = costs[la][key] + (lf - la) * slope
        out[f"{key}_per_layer"] = slope
    kinds = set(costs[la]["coll_by_kind"]) | set(costs[lb]["coll_by_kind"])
    out["coll_by_kind"] = {}
    for k in kinds:
        a = costs[la]["coll_by_kind"].get(k, 0)
        b = costs[lb]["coll_by_kind"].get(k, 0)
        out["coll_by_kind"][k] = int(a + (lf - la) * (b - a) / (lb - la))
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: Optional[str] = None, grad_sync: str = "auto",
             tag: str = "", with_roofline: Optional[bool] = None,
             cfg_overrides: Optional[Dict] = None) -> Dict:
    """Full-depth compile (fit proof) + roofline terms (single-pod cells)."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = cfg.scaled(**cfg_overrides)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell_id = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    if with_roofline is None:
        with_roofline = not multi_pod     # roofline table is single-pod only
    if not ok:
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                  "skipped": True, "reason": why}
    else:
        model = build_model(cfg)
        mf = _model_flops(model, shape)
        _, compiled, info = lower_cell(arch, shape_name, mesh,
                                       grad_sync=grad_sync, cfg=cfg)
        memdict, peak = _mem_dict(compiled)
        raw = _cell_costs(compiled)
        del compiled
        if with_roofline:
            ext = extrapolated_costs(arch, shape_name, mesh,
                                     grad_sync=grad_sync,
                                     cfg_overrides=cfg_overrides)
            cost = {"flops": ext["flops"], "bytes accessed": ext["bytes"]}
            wire = ext["wire"]
            coll = ext["coll_by_kind"]
        else:
            cost = {"flops": raw["flops"], "bytes accessed": raw["bytes"]}
            wire = raw["wire"]
            coll = raw["coll_by_kind"]
        chip = roofline.peaks(TARGET_KIND)
        rep = roofline.RooflineReport(
            arch=arch, shape=shape_name, mesh=info["mesh"],
            chips=info["chips"], device_kind=TARGET_KIND,
            flops_per_device=cost["flops"],
            bytes_per_device=cost["bytes accessed"],
            wire_bytes_per_device=wire,
            compute_s=cost["flops"] / chip.flops,
            memory_s=cost["bytes accessed"] / chip.hbm_bw,
            collective_s=wire / chip.link_bw,
            model_flops_global=mf,
            collectives=coll, peak_memory_bytes=peak)
        result = rep.to_dict()
        result["memory_analysis"] = memdict
        result["compile_s"] = info["compile_s"]
        result["grad_sync"] = grad_sync
        result["extrapolated"] = bool(with_roofline)
        result["raw_body_costs"] = raw
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, cell_id + ".json"), "w") as f:
            json.dump(result, f, indent=1)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--grad-sync", default="auto", choices=["auto", "int8"])
    ap.add_argument("--opt-attn", action="store_true",
                    help="enable attn_scale_in_q + attn_probs_bf16")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                cell = f"{arch} x {shape_name} x {'2x16x16' if mp else '16x16'}"
                t0 = time.perf_counter()
                try:
                    overrides = ({"attn_scale_in_q": True,
                                  "attn_probs_bf16": True}
                                 if args.opt_attn else None)
                    r = run_cell(arch, shape_name, multi_pod=mp,
                                 out_dir=args.out, grad_sync=args.grad_sync,
                                 tag=args.tag, cfg_overrides=overrides)
                    if r.get("skipped"):
                        print(f"[SKIP] {cell}: {r['reason']}", flush=True)
                    else:
                        print(f"[OK]   {cell}: cpu compile="
                              f"{r['compile_s']:.1f}s target={TARGET_KIND} "
                              f"dominant={r['dominant']} "
                              f"comp={r['compute_s']*1e3:.2f}ms "
                              f"mem={r['memory_s']*1e3:.2f}ms "
                              f"coll={r['collective_s']*1e3:.2f}ms "
                              f"useful={r['useful_flops_ratio']:.2f}",
                              flush=True)
                except Exception as e:
                    print(f"[FAIL] {cell}: {e}", flush=True)
                    traceback.print_exc()
                print(f"       wall={time.perf_counter()-t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
