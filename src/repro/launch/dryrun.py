import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (architecture x input-shape) on
the production meshes, print memory/cost analysis, and derive roofline terms.

Usage:
  python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
  python -m repro.launch.dryrun --all                  # every combo, 1-pod
  python -m repro.launch.dryrun --all --multipod       # every combo, 2 pods
Results are cached as JSON under results/dryrun/ (skip with --force).
"""
import argparse
import gzip
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs import ARCHS, INPUT_SHAPES, get_config, shape_applicable
from repro.launch.mesh import HBM_PER_CHIP, make_production_mesh, rules_for
from repro.launch.roofline import model_flops_estimate, roofline_from_compiled
from repro.launch.specs import abstract_state, token_pspecs, token_specs
from repro.models.api import build_model
from repro.models.pdefs import pspecs_from_defs
from repro.models.shardctx import activation_sharding
from repro.training.optimizer import AdamWConfig
from repro.training.steps import make_decode_step, make_prefill_step, make_train_step

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"
HLO_DIR = Path(__file__).resolve().parents[3] / "results" / "hlo"


def _tag(arch, shape_name, multi_pod, variant):
    tag = f"{arch}__{shape_name}__{'2x16x16' if multi_pod else '16x16'}"
    if variant != "base":
        tag += f"__{variant}"
    return tag


def _named(tree_pspecs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_pspecs,
                        is_leaf=lambda x: isinstance(x, PartitionSpec))


def apply_variant(cfg, variant: str):
    """§Perf variants (comma-combinable): config-level changes per
    optimization hypothesis."""
    import dataclasses
    parts = set(variant.split("+"))
    if "moe_ep" in parts and cfg.moe.n_experts:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, shard_mode="ep"))
    if "rwkv_chunk" in parts and cfg.family == "ssm":
        cfg = dataclasses.replace(cfg, rwkv_chunk=64)
    if "kv_int8" in parts:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    return cfg


def lower_combo(arch: str, shape_name: str, *, multi_pod: bool,
                variant: str = "base", cfg_override=None):
    """Build + lower + compile one (arch, shape, mesh). Returns result dict."""
    shape = INPUT_SHAPES[shape_name]
    cfg = cfg_override or get_config(arch)
    cfg = apply_variant(cfg, variant)
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    rules = rules_for(shape, variant)
    model = build_model(cfg, max_seq=shape.seq_len)

    state = abstract_state(model, shape, with_opt=(shape.kind == "train"))
    p_specs = pspecs_from_defs(model.param_defs(), mesh, rules)
    data = token_specs(cfg, shape)
    d_specs = token_pspecs(cfg, shape, mesh, rules)
    d_shard = {k: NamedSharding(mesh, v) for k, v in d_specs.items()}

    t0 = time.time()
    with mesh, activation_sharding(mesh, rules):
        if shape.kind == "train":
            step = make_train_step(model, AdamWConfig())
            opt_specs = {
                "mu": p_specs, "nu": p_specs, "step": PartitionSpec(),
            }
            batch = {k: data[k] for k in data}
            lowered = jax.jit(
                step,
                in_shardings=(_named(p_specs, mesh), _named(opt_specs, mesh),
                              d_shard),
            ).lower(state["params"], state["opt_state"], batch)
        elif shape.kind == "prefill":
            step = make_prefill_step(model)
            args = [state["params"], data["tokens"]]
            shards = [_named(p_specs, mesh), d_shard["tokens"]]
            if "memory" in data:
                args.append(data["memory"])
                shards.append(d_shard["memory"])
            lowered = jax.jit(step, in_shardings=tuple(shards)).lower(*args)
        else:  # decode
            step = make_decode_step(model)
            c_specs = pspecs_from_defs(state["cache_defs"], mesh, rules)
            lowered = jax.jit(
                step,
                in_shardings=(_named(p_specs, mesh), _named(c_specs, mesh),
                              d_shard["tokens1"], d_shard["positions"]),
                donate_argnums=(1,),   # in-place KV-cache update
            ).lower(state["params"], state["cache"], data["tokens1"],
                    data["positions"])
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    # archive the post-SPMD HLO so the roofline can be re-derived without
    # recompiling (analysis-model improvements, §Perf comparisons)
    HLO_DIR.mkdir(parents=True, exist_ok=True)
    hlo_path = HLO_DIR / (_tag(arch, shape_name, multi_pod, variant) + ".txt.gz")
    with gzip.open(hlo_path, "wt") as f:
        f.write(compiled.as_text())

    mf = model_flops_estimate(cfg, shape)
    rl = roofline_from_compiled(compiled, chips, mf,
                                pod_size=256 if multi_pod else chips)
    mem_txt = str(compiled.memory_analysis())

    res = {
        "status": "ok",
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "variant": variant,
        "chips": chips,
        "n_params": model.n_params(),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory_analysis": mem_txt,
        "roofline": rl.to_dict(),
        "fits_hbm": (rl.per_device_peak_memory < 0
                     or rl.per_device_peak_memory <= HBM_PER_CHIP),
    }
    return res


def reanalyze(arch, shape_name, multi_pod, variant):
    """Recompute roofline terms from the archived HLO (no recompilation)."""
    tag = _tag(arch, shape_name, multi_pod, variant)
    out = RESULTS / f"{tag}.json"
    hlo_path = HLO_DIR / (tag + ".txt.gz")
    if not (out.exists() and hlo_path.exists()):
        return None
    res = json.loads(out.read_text())
    if res.get("status") != "ok":
        return res
    from repro.launch.hlo_cost import analyze_hlo
    from repro.launch.roofline import Roofline
    shape = INPUT_SHAPES[shape_name]
    cfg = apply_variant(get_config(arch), variant)
    with gzip.open(hlo_path, "rt") as f:
        text = f.read()
    cost = analyze_hlo(text, pod_size=256 if multi_pod else 10 ** 9)
    old = res["roofline"]
    rl = Roofline(
        flops=cost.flops, bytes_accessed=cost.bytes,
        transcendentals=cost.transcendentals, ici_bytes=cost.ici_bytes,
        dcn_bytes=cost.dcn_bytes, chips=res["chips"],
        model_flops=model_flops_estimate(cfg, shape),
        coll_by_kind=dict(cost.coll_by_kind),
        xla_flops_unrolled=old.get("xla_flops_unrolled", -1.0),
        per_device_peak_memory=old.get("per_device_peak_memory", -1.0),
    )
    res["roofline"] = rl.to_dict()
    out.write_text(json.dumps(res, indent=1))
    return res


def run_one(arch, shape_name, multi_pod, variant, force=False, quiet=False,
            reanalyze_only=False):
    RESULTS.mkdir(parents=True, exist_ok=True)
    tag = _tag(arch, shape_name, multi_pod, variant)
    out = RESULTS / f"{tag}.json"
    if reanalyze_only:
        res = reanalyze(arch, shape_name, multi_pod, variant)
        if res is not None:
            if not quiet and res["status"] == "ok":
                rl = res["roofline"]
                print(f"[reanalyzed] {tag}: dominant={rl['dominant']} "
                      f"t=(c {rl['t_compute']:.3e}, m {rl['t_memory']:.3e}, "
                      f"coll {rl['t_collective']:.3e})")
            return res
        # fall through to a fresh compile when no archive exists
    if out.exists() and not force and not reanalyze_only:
        res = json.loads(out.read_text())
        if not quiet:
            print(f"[cached] {tag}: {res['status']}")
        return res
    try:
        res = lower_combo(arch, shape_name, multi_pod=multi_pod, variant=variant)
    except Exception as e:
        res = {"status": "error", "arch": arch, "shape": shape_name,
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    out.write_text(json.dumps(res, indent=1))
    if not quiet:
        if res["status"] == "ok":
            rl = res["roofline"]
            print(f"[ok] {tag}: compile={res['compile_s']}s "
                  f"dominant={rl['dominant']} "
                  f"t=(c {rl['t_compute']:.3e}, m {rl['t_memory']:.3e}, "
                  f"coll {rl['t_collective']:.3e}) useful={rl['useful_ratio']:.2f}")
        else:
            print(f"[{res['status']}] {tag}: {res.get('reason', res.get('error'))}")
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="base")
    ap.add_argument("--reanalyze", action="store_true",
                    help="recompute roofline from archived HLO")
    args = ap.parse_args()

    assert jax.device_count() >= 512, "dry-run needs the 512 fake devices"
    combos = []
    if args.all:
        for a in ARCHS:
            for s in INPUT_SHAPES:
                combos.append((a, s))
    else:
        assert args.arch and args.shape
        combos = [(args.arch, args.shape)]

    n_ok = n_skip = n_err = 0
    for a, s in combos:
        r = run_one(a, s, args.multipod, args.variant, args.force,
                    reanalyze_only=args.reanalyze)
        n_ok += r["status"] == "ok"
        n_skip += r["status"] == "skipped"
        n_err += r["status"] == "error"
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_err} errors")


if __name__ == "__main__":
    main()
