import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512").strip()
os.environ["JAX_PLATFORMS"] = "cpu"  # a modeling tool: never take a chip

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The three lines above MUST run before any jax import (device count locks on
first init). This proves the distribution config is coherent without real
hardware: a sharding mismatch, compile-time OOM, or unsupported collective
here is a bug in the system.

Usage:
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k --mesh single
  python -m repro.launch.dryrun --arch all --shape all --mesh both --out d.jsonl
"""

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs import ARCHS, get_config, input_specs, SHAPES
from repro.configs.shapes import cache_spec, shape_runnable
from repro.launch.costmodel import cell_costs
from repro.launch.mesh import enter_mesh, make_production_mesh
from repro.launch.roofline import (
    HW, collective_bytes, model_flops, roofline_terms)
from repro.launch.sharding import (
    batch_specs, cache_specs, count_bytes, state_specs, param_specs)
from repro.models.model import (
    decode_step, make_train_state, prefill_step, train_step)
from repro.models.shard_ctx import activation_sharding
from repro.models.transformer import init_params
from repro.optim.adamw import AdamWConfig

OPT = AdamWConfig()


def _sds(shapes_tree, spec_tree, mesh):
    return jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
        shapes_tree, spec_tree)


def count_params(cfg, params_shapes):
    """(total, active) param counts; expert weights scaled by top_k/E."""
    total = active = 0.0
    def visit(path, leaf):
        nonlocal total, active
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        n = float(leaf.size)
        total += n
        if "embed" in name or "head" in name:
            return
        if "moe" in name and ("w_in" in name or "w_out" in name) and "shared" not in name:
            n = n * cfg.moe.top_k / cfg.moe.num_experts
        active += n
    jax.tree_util.tree_map_with_path(visit, params_shapes)
    return total, active


def lower_cell(arch: str, shape_name: str, multi_pod: bool, fsdp_serve=None,
               variant: str = ""):
    """variant: comma-joined hillclimb levers applied on top of the config:
    'skip' (masked-block skipping), 'kvq' (int8 KV), 'zero1' (ZeRO-1
    sharding), 'accumN' (grad_accum=N)."""
    import dataclasses as _dc
    cfg = get_config(arch)
    state_mode = "fsdp"
    for v in [x for x in variant.split(",") if x]:
        if v == "skip":
            cfg = _dc.replace(cfg, skip_masked_blocks=True)
        elif v == "kvq":
            cfg = _dc.replace(cfg, kv_quant=True)
        elif v == "zero1":
            state_mode = "zero1"
        elif v.startswith("accum"):
            cfg = _dc.replace(cfg, grad_accum=int(v[5:]))
        else:
            raise ValueError(f"unknown variant {v}")
    shape = SHAPES[shape_name]
    ok, why = shape_runnable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    rec = {"arch": arch, "shape": shape_name, "variant": variant,
           "mesh": "multi" if multi_pod else "single", "devices": int(n_dev)}
    t0 = time.time()

    key = jax.random.PRNGKey(0)
    params_shapes = jax.eval_shape(lambda: init_params(key, cfg))
    total_p, active_p = count_params(cfg, params_shapes)
    rec["params_total"] = total_p
    rec["params_active"] = active_p

    # serving keeps params TP-only when they fit comfortably (< ~6 GB/chip
    # at bf16 over the model axis), else keeps the 2D (FSDP) layout.
    model_ax = 16
    serve_fsdp = (total_p * 2 / model_ax) > 6e9 if fsdp_serve is None else fsdp_serve

    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    with enter_mesh(mesh), activation_sharding(dp):
        if shape.kind == "train":
            state_shapes = jax.eval_shape(lambda: make_train_state(key, cfg))
            sspec = state_specs(state_shapes, mesh, fsdp=True, mode=state_mode)
            batch_shapes = input_specs(cfg, shape)
            bspec = batch_specs(batch_shapes, mesh)
            args = (_sds(state_shapes, sspec, mesh),
                    _sds(batch_shapes, bspec, mesh))
            lowered = train_step.lower(*args, cfg=cfg, opt_cfg=OPT)
            tokens = shape.batch * shape.seq
        elif shape.kind == "prefill":
            pspec = param_specs(params_shapes, mesh, fsdp=serve_fsdp)
            batch_shapes = input_specs(cfg, shape)
            bspec = batch_specs(batch_shapes, mesh)
            cshapes = cache_spec(cfg, shape)
            cspec = cache_specs(cshapes, mesh)
            args = (_sds(params_shapes, pspec, mesh),
                    _sds(batch_shapes, bspec, mesh),
                    _sds(cshapes, cspec, mesh))
            lowered = prefill_step.lower(*args, cfg=cfg)
            tokens = shape.batch * shape.seq
        else:  # decode
            pspec = param_specs(params_shapes, mesh, fsdp=serve_fsdp)
            inp = input_specs(cfg, shape)
            tspec = batch_specs({"tokens": inp["tokens"]}, mesh)["tokens"]
            cshapes = cache_spec(cfg, shape)
            cspec = cache_specs(cshapes, mesh)
            args = (_sds(params_shapes, pspec, mesh),
                    _sds({"t": inp["tokens"]}, {"t": tspec}, mesh)["t"],
                    _sds(cshapes, cspec, mesh),
                    jax.ShapeDtypeStruct((), jnp.int32,
                                         sharding=NamedSharding(
                                             mesh, jax.sharding.PartitionSpec())))
            lowered = decode_step.lower(*args, cfg=cfg)
            tokens = shape.batch
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    ca = compiled.cost_analysis() or {}
    # NOTE: XLA counts while-loop bodies once (verified experimentally), so
    # these raw numbers undercount scanned models; the roofline terms below
    # use the loop-aware analytic model (launch/costmodel.py), calibrated
    # against XLA on unrolled configs in tests/test_costmodel.py.
    rec["flops_hlo_raw"] = float(ca.get("flops", 0.0))
    rec["bytes_hlo_raw"] = float(ca.get("bytes accessed", 0.0))
    cost = cell_costs(cfg, shape.kind, shape.seq, shape.batch,
                      n_devices=n_dev, model_ax=16,
                      dp_ax=n_dev // 16, fsdp=(shape.kind == "train" or serve_fsdp),
                      state_mode=state_mode)
    rec["flops_per_dev"] = cost.flops_per_dev
    rec["bytes_per_dev"] = cost.bytes_per_dev
    rec["coll_bytes_analytic"] = cost.coll_bytes_per_dev
    ma = compiled.memory_analysis()
    if ma is not None:
        rec["mem"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
        resident = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                    + ma.output_size_in_bytes - ma.alias_size_in_bytes)
        rec["mem"]["resident_bytes"] = int(resident)
        rec["mem"]["fits_hbm"] = bool(resident < HW().hbm_bytes)
    txt = compiled.as_text()
    coll = collective_bytes(txt)
    rec["collectives_hlo_raw"] = {k: float(v) for k, v in coll.items()}
    rec["hlo_bytes"] = len(txt)

    terms = roofline_terms(
        rec["flops_per_dev"], rec["bytes_per_dev"],
        max(cost.coll_bytes_per_dev, coll.get("total", 0.0)))
    rec.update(terms)
    mf = model_flops(active_p, tokens, shape.kind)
    rec["model_flops_total"] = mf
    rec["model_flops_per_dev"] = mf / n_dev
    if rec["flops_per_dev"] > 0:
        rec["useful_flops_ratio"] = rec["model_flops_per_dev"] / rec["flops_per_dev"]
    rec["status"] = "ok"
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=None)
    ap.add_argument("--variant", default="")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args()

    archs = ARCHS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    out_f = open(args.out, "a") if args.out else None
    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                try:
                    rec = lower_cell(arch, shape, multi, variant=args.variant)
                except Exception as e:  # a failure here is a system bug
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "multi" if multi else "single",
                           "status": "error", "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                    n_fail += 1
                jax.clear_caches()   # keep the 80-cell sweep's RSS bounded
                line = json.dumps(rec)
                if out_f:
                    out_f.write(line + "\n")
                    out_f.flush()
                if not args.quiet:
                    brief = {k: rec.get(k) for k in
                             ("arch", "shape", "mesh", "status", "compile_s",
                              "dominant", "compute_fraction", "error")}
                    print(json.dumps(brief))
                if rec.get("mem"):
                    print(f"  memory_analysis: resident={rec['mem']['resident_bytes']/1e9:.2f}GB "
                          f"fits_hbm={rec['mem']['fits_hbm']}", file=sys.stderr)
    if out_f:
        out_f.close()
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
