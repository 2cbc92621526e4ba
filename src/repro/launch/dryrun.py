import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape) on the
production mesh; dump memory/cost/collective analysis for §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out results/dryrun]
"""  # noqa: E402

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, ScalaConfig, get_config, get_shape  # noqa: E402
from repro.core.scala import (scala_local_step_fused,  # noqa: E402
                              scala_local_step_fused_dp,
                              transformer_split_model)
from repro.launch import input_specs as ispec  # noqa: E402
from repro.launch.mesh import make_production_mesh, num_clients_for  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.perf import roofline  # noqa: E402
from repro.sharding.logical import rules_for, tree_shardings, tree_specs  # noqa: E402


def skip_reason(cfg, shape) -> str:
    if shape.name == "long_500k" and not cfg.supports_long_decode:
        return "SKIP(full-attn): pure full-attention stack; 512k decode " \
               "requires sub-quadratic attention (see DESIGN.md §4)"
    return ""


def _replicated_tree(tree, mesh):
    rep = NamedSharding(mesh, PartitionSpec())
    return jax.tree.map(lambda _: rep, tree)


def build_step(arch: str, shape_name: str, mesh, *, remat=True,
               scala_overrides=None):
    """Returns (fn, args_shapes, in_shardings, out_shardings, meta)."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    meta = {"arch": arch, "shape": shape_name, "mode": shape.mode,
            "sharding_profile": cfg.sharding_profile}
    rules = rules_for(cfg.sharding_profile)

    if shape.mode == "train":
        C = num_clients_for(mesh)
        params_sh, params_ax = ispec.param_specs(cfg, num_clients=C)
        batch_sh, batch_ax = ispec.train_batch_specs(cfg, shape, C)
        p_shard = tree_shardings(params_ax, params_sh, mesh, rules)
        b_shard = tree_shardings(batch_ax, batch_sh, mesh, rules)
        model = transformer_split_model(cfg, remat=remat)
        sc = ScalaConfig(**(scala_overrides or {}))

        if cfg.sharding_profile == "dp":
            # manual-SPMD step: one grad psum per step (§Perf)
            b_specs = tree_specs(batch_ax, batch_sh, mesh, rules)

            def step(params, batch):
                return scala_local_step_fused_dp(model, params, batch, sc,
                                                 mesh, b_specs)
        else:
            def step(params, batch):
                return scala_local_step_fused(model, params, batch, sc)

        metrics_shapes = jax.eval_shape(step, params_sh, batch_sh)[1]
        out_sh = (p_shard, _replicated_tree(metrics_shapes, mesh))
        meta["num_clients"] = C
        meta["tokens"] = shape.global_batch * shape.seq_len
        return step, (params_sh, batch_sh), (p_shard, b_shard), out_sh, meta, cfg

    # ZeRO/FSDP is a *training* sharding: per-layer weight gathers are
    # amortized over the huge train batch. Serving (prefill/decode) keeps
    # the TP layout — measured: fsdp prefill is 30-90x worse on
    # collectives and fsdp decode replicates the KV cache. The dp profile
    # serves prefill fine (plain data-parallel serving) but its decode
    # cache needs the TP kv-head sharding (§Perf-beyond).
    if cfg.sharding_profile == "fsdp":
        rules = rules_for("tp")
    elif cfg.sharding_profile == "dp" and shape.mode == "decode":
        rules = rules_for("tp")

    if shape.mode == "prefill":
        params_sh, params_ax = ispec.param_specs(cfg)
        batch_sh, batch_ax = ispec.prefill_batch_specs(cfg, shape)
        p_shard = tree_shardings(params_ax, params_sh, mesh, rules)
        b_shard = tree_shardings(batch_ax, batch_sh, mesh, rules)

        def step(params, batch):
            return T.forward_prefill(params, batch, cfg)

        meta["tokens"] = shape.global_batch * shape.seq_len
        return step, (params_sh, batch_sh), (p_shard, b_shard), None, meta, cfg

    # decode
    params_sh, params_ax = ispec.param_specs(cfg)
    batch_sh, batch_ax, cache_sh, cache_ax = ispec.decode_batch_specs(cfg, shape)
    p_shard = tree_shardings(params_ax, params_sh, mesh, rules)
    b_shard = tree_shardings(batch_ax, batch_sh, mesh, rules)
    c_shard = tree_shardings(cache_ax, cache_sh, mesh, rules)
    idx_sh = jax.ShapeDtypeStruct((), jnp.int32)
    rep = NamedSharding(mesh, PartitionSpec())

    def step(params, batch, cache, index):
        return T.decode_step(params, batch, cache, index, cfg)

    out_sh = (None, c_shard)
    meta["tokens"] = shape.global_batch  # one token per sequence
    return (step, (params_sh, batch_sh, cache_sh, idx_sh),
            (p_shard, b_shard, c_shard, rep), out_sh, meta, cfg)


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               remat: bool = True, scala_overrides=None,
               keep_hlo: bool = False) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_name}

    reason = skip_reason(cfg, shape)
    if reason:
        record["status"] = "skip"
        record["reason"] = reason
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    try:
        step, args, in_sh, out_sh, meta, cfg = build_step(
            arch, shape_name, mesh, remat=remat,
            scala_overrides=scala_overrides)
        record.update(meta)

        t0 = time.time()
        with jax.set_mesh(mesh):
            jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            t0 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t0

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = roofline.parse_collectives(hlo)
        coll_scoped = roofline.parse_collectives_scoped(hlo)

        flops = float(cost.get("flops", 0.0))
        bytes_acc = float(cost.get("bytes accessed", 0.0))
        min_bytes = float((getattr(mem, "argument_size_in_bytes", 0) or 0)
                          + (getattr(mem, "output_size_in_bytes", 0) or 0)
                          + (getattr(mem, "peak_memory_in_bytes", 0) or 0))
        terms = roofline.roofline_terms(flops, bytes_acc,
                                        coll["total_bytes"], min_bytes)
        terms_scoped = roofline.roofline_terms(
            flops, bytes_acc, coll_scoped["total_bytes"], min_bytes)

        params_sh, params_ax = (args[0], None)
        # model flops (active params)
        p_shapes, p_axes = ispec.param_specs(
            cfg, num_clients=meta.get("num_clients", 0))
        counts = roofline.count_params(
            p_shapes, p_axes,
            top_k=cfg.moe.top_k if cfg.moe else 0,
            num_experts=cfg.moe.num_experts if cfg.moe else 0)
        mf = roofline.model_flops(counts["active"], meta["tokens"],
                                  "train" if shape.mode == "train" else "serve")

        record.update({
            "status": "ok",
            "chips": chips,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "flops_per_device": flops,
            "bytes_per_device": bytes_acc,
            "collectives": coll,
            "collectives_scoped": coll_scoped,
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
                "generated_code_bytes": getattr(
                    mem, "generated_code_size_in_bytes", None),
            },
            "fits_hbm": bool(
                ((getattr(mem, "argument_size_in_bytes", 0) or 0)
                 + (getattr(mem, "output_size_in_bytes", 0) or 0)
                 + (getattr(mem, "peak_memory_in_bytes", 0) or 0))
                < 16e9),
            "roofline": terms,
            "roofline_scoped": terms_scoped,
            "params_total": counts["total"],
            "params_active": counts["active"],
            "model_flops_global": mf,
            "model_flops_per_device": mf / chips,
            "useful_flops_ratio": (mf / chips) / flops if flops else None,
        })
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--force", action="store_true",
                    help="re-run even if a cached ok/skip record exists")
    ap.add_argument("--no-constrain", action="store_true",
                    help="disable in-graph activation sharding constraints "
                         "(reproduces the propagation-only §Perf baseline)")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    if args.no_constrain:
        from repro.sharding import logical
        logical.CONSTRAIN = False

    pairs = []
    archs = ASSIGNED_ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                pairs.append((a, s, mp))

    os.makedirs(args.out, exist_ok=True)
    for arch, shape, mp in pairs:
        mesh_name = "2x16x16" if mp else "16x16"
        path = os.path.join(args.out, f"{arch}__{shape}__{mesh_name}.json")
        if os.path.exists(path) and not args.force:
            with open(path) as f:
                prev = json.load(f)
            if prev.get("status") in ("ok", "skip"):
                print(f"[cached] {arch} {shape} {mesh_name}: {prev['status']}")
                continue
        rec = dryrun_one(arch, shape, multi_pod=mp, remat=not args.no_remat)
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
        status = rec["status"]
        extra = ""
        if status == "ok":
            r = rec["roofline"]
            extra = (f" compile={rec['compile_s']}s bottleneck={r['bottleneck']}"
                     f" tc={r['t_compute_s']:.3e} tm={r['t_memory_s']:.3e}"
                     f" tx={r['t_collective_s']:.3e}")
        elif status == "error":
            extra = " " + rec["error"][:200]
        print(f"[{status}] {arch} {shape} {mesh_name}{extra}", flush=True)


if __name__ == "__main__":
    main()
