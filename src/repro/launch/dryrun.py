import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST precede every other import: jax locks the platform
# and device count at first init. (Only the dry-run forces 512 placeholder
# host devices — tests and benchmarks see the real single CPU device.)
if os.environ.get("DRYRUN_DEVICES"):       # test hook (jax not imported yet)
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware:
  * the sharding config is coherent (no mismatched collectives),
  * the program fits (memory_analysis bytes/device vs the 16 GB v5e HBM),
  * and yields the roofline terms (cost_analysis + HLO collective bytes).

Usage::

  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh multi      # every applicable cell
"""
import argparse
import dataclasses
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, TrainConfig
from repro.configs.registry import cell_is_applicable, get_config
from repro.launch.mesh import dp_size, make_production_mesh
from repro.launch.specs import (
    batch_spec_tree, named, sanitize_specs, serve_input_specs,
    train_input_specs)
from repro.models.sharding import param_specs
from repro.models.transformer import forward, init_params, loss_fn
from repro.roofline.analysis import analyze
from repro.train.optimizer import init_adam
from repro.train.train_step import make_train_step
from repro.train import train_step as ts_mod

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _params_shapes(cfg, dtype=jnp.bfloat16):
    return jax.eval_shape(
        functools.partial(init_params, cfg, dtype=dtype),
        jax.random.PRNGKey(0))


def _make_mesh(mesh_kind: str):
    """'single' | 'multi' | custom 'S1xS2[xS3]:ax1,ax2[,ax3]'."""
    if mesh_kind in ("single", "multi"):
        return make_production_mesh(multi_pod=(mesh_kind == "multi"))
    shape_s, axes_s = mesh_kind.split(":")
    shape = tuple(int(x) for x in shape_s.split("x"))
    axes = tuple(axes_s.split(","))
    from repro.launch.mesh import make_mesh
    return make_mesh(shape, axes)


def lower_cell(arch: str, shape_name: str, mesh_kind: str,
               tcfg: TrainConfig, save_hlo: str = "",
               bucketed: bool = False):
    """Lower+compile one cell; returns (roofline, mem_stats, meta)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = _make_mesh(mesh_kind)
    chips = mesh.size
    tp = mesh.shape["model"]

    with jax.set_mesh(mesh):
        p_shapes = _params_shapes(cfg)
        p_specs = sanitize_specs(param_specs(p_shapes), p_shapes, mesh)
        p_shard = named(p_specs, mesh)

        t0 = time.time()
        if shape.kind == "train":
            in_specs = train_input_specs(cfg, shape)
            b_shard = named(batch_spec_tree(cfg, in_specs, mesh,
                                            shape.global_batch), mesh)
            opt_shapes = jax.eval_shape(init_adam, p_shapes)
            from repro.train.optimizer import zero1_specs
            dp_axes = tuple(a for a in ("pod", "data")
                            if a in mesh.axis_names)
            o_specs = (zero1_specs(p_shapes, p_specs, dp_axes,
                                   dp_size(mesh))
                       if tcfg.zero1 else p_specs)
            o_shard = jax.tree.map(
                lambda s: NamedSharding(mesh, s),
                type(opt_shapes)(P(), o_specs, o_specs),
                is_leaf=lambda x: isinstance(x, P))
            if bucketed:
                from repro.launch.mesh import make_production_mesh as _m
                step = ts_mod.make_bucketed_train_step(cfg, tcfg, mesh)
                res_shapes = jax.eval_shape(
                    lambda p: jax.tree.map(
                        lambda x: jnp.zeros(x.shape, jnp.float32), p),
                    p_shapes)
                fn = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard,
                                                 None),
                             donate_argnums=(0, 1))
                lowered = fn.lower(p_shapes, opt_shapes, in_specs,
                                   res_shapes)
            else:
                step = make_train_step(cfg, tcfg, mesh)
                fn = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                             donate_argnums=(0, 1))
                lowered = fn.lower(p_shapes, opt_shapes, in_specs)
        else:
            kind = "prefill" if shape.kind == "prefill" else "decode"
            in_specs = serve_input_specs(cfg, shape, kind)
            all_specs = batch_spec_tree(cfg, in_specs, mesh,
                                        shape.global_batch)
            caches = in_specs.pop("caches")
            c_shard = named(all_specs.pop("caches"), mesh)
            b_shard = named(all_specs, mesh)

            if kind == "prefill":
                def fn_impl(params, batch, caches):
                    from repro.serve.serve_step import prefill_step
                    return prefill_step(params, cfg, batch, caches)
            else:
                def fn_impl(params, batch, caches):
                    from repro.serve.serve_step import decode_step
                    pos = batch.pop("pos")
                    toks = batch.pop("tokens")
                    return decode_step(params, cfg, toks, caches, pos,
                                       extra=batch or None)
            fn = jax.jit(fn_impl, in_shardings=(p_shard, b_shard, c_shard),
                         donate_argnums=(2,))
            lowered = fn.lower(p_shapes, in_specs, caches)

        compiled = lowered.compile()
        compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):   # jax<=0.4 returns [dict] per program
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)

    mem_gb = -1.0
    mem_dict = {}
    if mem is not None:
        mem_dict = {k: int(getattr(mem, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes") if hasattr(mem, k)}
        live = (mem_dict.get("argument_size_in_bytes", 0)
                + mem_dict.get("temp_size_in_bytes", 0)
                + mem_dict.get("output_size_in_bytes", 0)
                - mem_dict.get("alias_size_in_bytes", 0))
        mem_gb = live / 1e9

    roof = analyze(arch, shape_name, mesh_kind, chips, cost, hlo, cfg,
                   shape, tp, compile_s, mem_gb)
    return roof, mem_dict, {"hlo_chars": len(hlo)}


def run_cell(arch, shape_name, mesh_kind, tcfg, out_dir, bucketed=False,
             save_hlo="", name_tag=""):
    ok, why = cell_is_applicable(arch, shape_name)
    tag = f"{arch}|{shape_name}|{mesh_kind}"
    if not ok:
        print(f"SKIP {tag}: {why}")
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "skipped": why}
    try:
        roof, mem_dict, meta = lower_cell(arch, shape_name, mesh_kind,
                                          tcfg, save_hlo, bucketed)
        rec = dataclasses.asdict(roof)
        rec.update({"memory": mem_dict, "ok": True, **meta})
        print(f"OK   {tag}: {roof.row()}  mem={roof.memory_per_device_gb:.2f}GB"
              f"  compile={roof.compile_seconds:.0f}s")
    except Exception as e:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "ok": False, "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-2000:]}
        print(f"FAIL {tag}: {type(e).__name__}: {e}")
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch}_{shape_name}_{mesh_kind}".replace(".", "_")
    if bucketed:
        fname += "_bucketed"
    if name_tag:
        fname += "_" + name_tag
    with open(os.path.join(out_dir, fname + ".json"), "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--shape", default="train_4k",
                    choices=list(SHAPES) + ["all"])
    ap.add_argument("--mesh", default="single",
                    help="single | multi | both | SHAPE:AXES "
                         "(e.g. 2x4:data,model)")
    ap.add_argument("--all", action="store_true",
                    help="run every applicable (arch x shape) cell")
    ap.add_argument("--out", default=os.path.abspath(OUT_DIR))
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--no-seq-parallel", action="store_true")
    ap.add_argument("--bucketed", action="store_true",
                    help="doorbell-batched explicit grad sync (shard_map)")
    ap.add_argument("--bucket-mb", type=float, default=16.0)
    ap.add_argument("--save-hlo", default="")
    ap.add_argument("--attn", default="naive",
                    choices=["naive", "blockwise"],
                    help="attention lowering (perf knob, §Perf)")
    ap.add_argument("--attn-chunk", type=int, default=2048)
    ap.add_argument("--remat-policy", default="full",
                    choices=["full", "dots", "none"],
                    help="activation-checkpoint policy (perf knob)")
    ap.add_argument("--no-qkv-shard", action="store_true",
                    help="disable explicit 4-D q/k/v sharding (= the "
                         "paper-faithful baseline lowering)")
    ap.add_argument("--tag", default="",
                    help="suffix for result filenames (perf experiments)")
    args = ap.parse_args()

    if args.attn != "naive":
        from repro.models.layers import set_attention_impl
        set_attention_impl(args.attn, args.attn_chunk)
    if args.no_qkv_shard:
        from repro.models.sharding import set_qkv_sharding
        set_qkv_sharding(False)
    if args.remat_policy != "full":
        from repro.models.transformer import set_remat_policy
        set_remat_policy(args.remat_policy)

    tcfg = TrainConfig(
        microbatches=args.microbatches, remat=not args.no_remat,
        zero1=not args.no_zero1,
        sequence_parallel=not args.no_seq_parallel,
        grad_bucket_mb=args.bucket_mb, param_dtype="bfloat16")

    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])
    if args.all:
        from repro.configs.registry import ARCHS
        cells = [(a, s) for a in ARCHS for s in SHAPES]
    elif args.shape == "all":
        cells = [(args.arch, s) for s in SHAPES]
    else:
        cells = [(args.arch, args.shape)]

    results = []
    for arch, shape_name in cells:
        for mk in meshes:
            results.append(run_cell(arch, shape_name, mk, tcfg, args.out,
                                    args.bucketed, args.save_hlo,
                                    args.tag))
    n_ok = sum(1 for r in results if r.get("ok"))
    n_skip = sum(1 for r in results if "skipped" in r)
    n_fail = len(results) - n_ok - n_skip
    print(f"\ndone: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
