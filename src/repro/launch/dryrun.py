import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first lines, before ANY jax-importing import: jax locks the
# device count at first initialization. Do not set this flag anywhere else
# (smoke tests and benches must see 1 device).

"""Multi-pod dry-run: lower + compile every (arch × shape) on the production
meshes and extract the roofline terms from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2_1p5b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all            # 40-cell sweep
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi

Per cell this produces artifacts/dryrun/<arch>_<shape>_<mesh>.json with:
  * memory_analysis (per-device bytes: args/outputs/temps) — proves fit,
  * cost_analysis (per-device HLO FLOPs + bytes accessed),
  * collective bytes by op type, parsed from compiled.as_text() with
    while-loop (lax.scan) trip-count multiplication,
  * MODEL_FLOPS (6·N·D dense / 6·N_active·D MoE) and the useful-compute ratio.
"""
import argparse
import dataclasses
import json
import re
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.distributed import sharding as Sh
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh, data_axes
from repro.models import model as M
from repro.train import optimizer as opt
from repro.train import step as TS

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")

# microbatch accumulation for the models whose per-layer saved stacks +
# transients exceed HBM at full batch (§Perf lever; divides activation
# memory by the factor at the cost of an f32 grad-accumulation buffer)
GRAD_ACCUM = {
    "qwen3_moe_235b_a22b": 8,
    "llava_next_34b": 2,   # §Perf C1/C2: -47% collective vs accum=4
    "zamba2_1p2b": 2,
}

# §Perf-adopted per-arch train-time q_chunk (EXPERIMENTS.md §Perf):
# chunking costs k/v re-reads per chunk, so it only pays where the f32
# score block would otherwise blow HBM (musicgen's 32 full heads, zamba's
# shared block, llava/qwen3 at their batch sizes). 0 = unchunked.
Q_CHUNK_TRAIN = {
    "chatglm3_6b": 0, "yi_9b": 0, "granite_moe_3b_a800m": 0,
    "smollm_360m": 0, "qwen2_1p5b": 0, "xlstm_350m": 0,
    "musicgen_large": 1024, "zamba2_1p2b": 1024,
    "llava_next_34b": 2048, "qwen3_moe_235b_a22b": 1024,
}
# bf16 optimizer moments + bf16 grad accumulation for the 235B config:
# f32 moments alone are 7.3 GiB/device at this scale (Gopher-style recipe)
BF16_STATE = {"qwen3_moe_235b_a22b"}

# ---------------------------------------------------------------------------
# Cell construction
# ---------------------------------------------------------------------------


def exec_config(cfg: M.ModelConfig, shape: str, mesh, arch: str = "") -> M.ModelConfig:
    """Execution-tuned config for a dry-run cell (remat + activation sharding)."""
    seq, gb, kind = configs.SHAPES[shape]
    axes = data_axes(mesh)
    dp = 1
    for a in axes:
        dp *= mesh.shape[a]
    batch_axes = axes if (gb % dp == 0 and gb >= dp) else None
    seq_axis = None
    if kind in ("train", "prefill") and "model" in mesh.axis_names:
        if seq % mesh.shape["model"] == 0:
            seq_axis = "model"
    remat = "full" if kind == "train" else "none"
    # q-chunked attention bounds f32 score memory (scores are the largest
    # train-time temporary at seq>=4k: [b,kv,rep,q,l] f32)
    q_chunk = 1024 if (kind in ("train", "prefill") and seq >= 4096) else 0
    if kind == "train" and arch in Q_CHUNK_TRAIN:
        q_chunk = Q_CHUNK_TRAIN[arch]
    # MoE dispatch-buffer sharding: EP when n_experts divides the model
    # axis; else shard the capacity dim (expert-TP fallback)
    moe_e, moe_c, e_mult = None, None, 1
    if cfg.n_experts and "model" in mesh.axis_names:
        msz = mesh.shape["model"]
        moe_e = "model"  # EP via all-to-all (shard_map); phantom-pad experts
        e_mult = msz
    score_axis = None  # context-parallel scores: a §Perf lever, off by default
    ssm_axis = None  # SSD head sharding: §Perf lever; nc stays seq-sharded
    vocab_axis = None
    if "model" in mesh.axis_names and cfg.padded_vocab % mesh.shape["model"] == 0:
        vocab_axis = "model"
    return dataclasses.replace(
        cfg, remat=remat, act_batch_axes=batch_axes, act_seq_axis=seq_axis,
        q_chunk=q_chunk, moe_expert_axis=moe_e, moe_cap_axis=moe_c,
        ssm_head_axis=ssm_axis, expert_pad_multiple=e_mult,
        score_seq_axis=score_axis, vocab_axis=vocab_axis)


def _replicated(mesh):
    return NamedSharding(mesh, P())


def build_cell(arch: str, shape: str, mesh):
    """Returns (jitted_fn, arg_shapes, donate) ready to .lower(*arg_shapes)."""
    cfg = exec_config(configs.get_config(arch), shape, mesh, arch=arch)
    seq, gb, kind = configs.SHAPES[shape]

    if kind == "train":
        big = arch in BF16_STATE
        tcfg = TS.TrainConfig(adamw=opt.AdamWConfig(),
                              grad_accum=GRAD_ACCUM.get(arch, 1),
                              opt_state_dtype=jnp.bfloat16 if big else jnp.float32,
                              accum_dtype=jnp.bfloat16 if big else jnp.float32)
        state_shapes = jax.eval_shape(
            lambda k: TS.init_train_state(cfg, tcfg, k), jax.random.PRNGKey(0))
        state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                Sh.param_shardings(state_shapes, mesh))
        batch_shapes = configs.input_specs(cfg, shape)
        batch_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                Sh.batch_shardings(batch_shapes, mesh, gb))
        fn = TS.make_train_step(cfg, tcfg)
        jitted = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, _replicated(mesh)),
                         donate_argnums=(0,))
        return jitted, (state_shapes, batch_shapes), cfg

    if kind == "prefill":
        params_shapes = jax.eval_shape(
            lambda k: M.init_params(cfg, k), jax.random.PRNGKey(0))
        params_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 Sh.param_shardings(params_shapes, mesh))
        batch_shapes = configs.input_specs(cfg, shape)
        batch_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                Sh.batch_shardings(batch_shapes, mesh, gb))
        fn = lambda p, b: M.forward(p, cfg, b, last_only=True)
        jitted = jax.jit(fn, in_shardings=(params_sh, batch_sh))
        return jitted, (params_shapes, batch_shapes), cfg

    # decode
    params_shapes = jax.eval_shape(
        lambda k: M.init_params(cfg, k), jax.random.PRNGKey(0))
    params_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             Sh.param_shardings(params_shapes, mesh))
    batch_shapes, cache_shapes, pos_shape = configs.input_specs(cfg, shape)
    batch_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                            Sh.batch_shardings(batch_shapes, mesh, gb))
    cache_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                            Sh.cache_shardings(cache_shapes, mesh, gb))
    fn = lambda p, c, b, pos: M.decode_step(p, cfg, c, b, pos)
    # out cache sharding == in cache sharding -> donation aliases the cache
    jitted = jax.jit(fn, in_shardings=(params_sh, cache_sh, batch_sh,
                                       _replicated(mesh)),
                     out_shardings=(None, cache_sh), donate_argnums=(1,))
    return jitted, (params_shapes, cache_shapes, batch_shapes, pos_shape), cfg


def model_flops(cfg: M.ModelConfig, shape: str) -> float:
    """MODEL_FLOPS: 6·N·D train (N=active params), 2·N·B per decoded token."""
    seq, gb, kind = configs.SHAPES[shape]
    params_shapes = jax.eval_shape(
        lambda k: M.init_params(cfg, k), jax.random.PRNGKey(0))
    n_active = M.active_param_count(cfg, params_shapes)
    tokens = gb * seq
    if kind == "train":
        return 6.0 * n_active * tokens
    if kind == "prefill":
        return 2.0 * n_active * tokens
    return 2.0 * n_active * gb  # decode: one token per sequence


def run_cell(arch: str, shape: str, multi_pod: bool) -> Dict[str, Any]:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "multi" if multi_pod else "single"
    cfg0 = configs.get_config(arch)
    ok, why = configs.shape_applicable(cfg0, shape)
    rec: Dict[str, Any] = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "n_devices": mesh.size}
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec

    t0 = time.perf_counter()
    jitted, arg_shapes, cfg = build_cell(arch, shape, mesh)
    # the ambient mesh must be visible inside jit tracing — the MoE
    # shard_map paths key off it
    with jax.sharding.set_mesh(mesh):
        lowered = jitted.lower(*arg_shapes)
        t_lower = time.perf_counter() - t0
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t1

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    t2 = time.perf_counter()
    parsed = hlo_cost.analyze(compiled.as_text())
    t_parse = time.perf_counter() - t2

    rec.update({
        "status": "ok",
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_bytes": mem.argument_size_in_bytes
            + mem.temp_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        # raw XLA numbers (CAVEAT: while bodies counted once — see hlo_cost)
        "xla_cost_raw": {
            "flops_per_device": cost.get("flops", 0.0),
            "bytes_accessed_per_device": cost.get("bytes accessed", 0.0),
        },
        # loop-aware totals parsed from compiled HLO (per device)
        "cost": {
            "flops_per_device": parsed["flops"],
            "bytes_accessed_per_device": parsed["bytes"],
            "parse_s": round(t_parse, 2),
        },
        "collectives": {
            "bytes_by_type": parsed["collective_bytes_by_type"],
            "count_by_type": parsed["collective_count_by_type"],
            "total_bytes": parsed["collective_bytes_total"],
        },
        "model_flops_global": model_flops(cfg, shape),
        "act_seq_axis": cfg.act_seq_axis,
        "remat": cfg.remat,
    })
    hbm = 16 * 1024**3
    rec["fits_16GiB_hbm"] = rec["memory"]["peak_estimate_bytes"] <= hbm
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = configs.ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(configs.SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = 0
    for mesh_name in meshes:
        for arch in archs:
            for shape in shapes:
                out_path = os.path.join(args.out, f"{arch}_{shape}_{mesh_name}.json")
                if os.path.exists(out_path):
                    print(f"[dryrun] {arch} × {shape} × {mesh_name}: cached")
                    continue
                print(f"[dryrun] {arch} × {shape} × {mesh_name} ...", flush=True)
                try:
                    rec = run_cell(arch, shape, multi_pod=(mesh_name == "multi"))
                except Exception as e:  # noqa: BLE001 - report and continue
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "status": "error", "error": repr(e)[:2000]}
                    failures += 1
                with open(out_path, "w") as f:
                    json.dump(rec, f, indent=1)
                if rec["status"] == "ok":
                    m = rec["memory"]
                    print(f"  ok: compile {rec['compile_s']}s, "
                          f"peak/device {m['peak_estimate_bytes']/2**30:.2f} GiB, "
                          f"flops/device {rec['cost']['flops_per_device']:.3e}, "
                          f"coll {rec['collectives']['total_bytes']/2**30:.3f} GiB",
                          flush=True)
                elif rec["status"] == "skipped":
                    print(f"  skipped: {rec['reason']}")
                else:
                    print(f"  ERROR: {rec['error'][:300]}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
