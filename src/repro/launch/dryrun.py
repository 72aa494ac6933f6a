import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay first: jax locks the device count at first
initialization, and the production meshes need 512 host platform devices.

For every supported cell this script:
  1. builds the full-size model spec (ShapeDtypeStructs — no allocation),
  2. constructs the per-(arch, step) sharding policy and PartitionSpecs,
  3. jit(step).lower(...).compile() under the target mesh,
  4. records memory_analysis / cost_analysis / the collective-bytes
     census into results/dryrun/<arch>__<shape>__<mesh>.json.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun                  # every cell, both meshes
  PYTHONPATH=src python -m repro.launch.dryrun --mesh pod       # single-pod only
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, cell_supported, get_config
from repro.distributed.policies import make_policy
from repro.distributed.sharding import use_sharding
from repro.launch import shardings as shd
from repro.launch.hlo_analysis import collective_bytes, roofline_terms
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_decode_step, make_prefill_step, make_train_step
from repro.models import LM
from repro.training.optimizer import OptimizerConfig, init_opt_state

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _opt_cfg(cfg) -> OptimizerConfig:
    # int8 moments for the 400B MoE: the only way a single-pod v5e fits
    # params + AdamW state (see EXPERIMENTS.md §Dry-run).
    quantize = cfg.param_count() > 100e9
    return OptimizerConfig(quantize_moments=quantize)


def _abstract_opt_state(model, opt_cfg):
    """Optimizer-state ShapeDtypeStructs without materializing params."""
    params = model.abstract_params()
    return jax.eval_shape(lambda p: init_opt_state(p, opt_cfg), params)


def run_cell(arch: str, shape_name: str, mesh_kind: str, force: bool = False) -> dict:
    import dataclasses

    cfg = get_config(arch)
    chunk_override = int(os.environ.get("REPRO_ATTN_CHUNK", "0"))
    if chunk_override:
        cfg = dataclasses.replace(
            cfg, attn_q_chunk=chunk_override, attn_kv_chunk=chunk_override)
    if os.environ.get("REPRO_KV_QUANT") == "1":
        cfg = dataclasses.replace(cfg, kv_quant=True)
    shape = SHAPES[shape_name]
    suffix = os.environ.get("REPRO_CELL_SUFFIX", "")
    out_path = RESULTS / f"{cfg.name}__{shape_name}__{mesh_kind}{suffix}.json"
    ok, reason = cell_supported(cfg.name, shape_name)
    if not ok:
        rec = {"arch": cfg.name, "shape": shape_name, "mesh": mesh_kind,
               "status": "skipped", "reason": reason}
        out_path.parent.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(rec, indent=2))
        return rec
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    n_dev = mesh.devices.size
    policy = make_policy(cfg, shape.step, mesh)
    model = LM(cfg)
    t0 = time.time()
    rec = {
        "arch": cfg.name, "shape": shape_name, "mesh": mesh_kind,
        "mesh_shape": dict(mesh.shape), "step": shape.step,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
    }
    # §Perf hillclimb knobs (env): REPRO_ATTN_UNROLL_SKIP=1 switches the
    # attention implementation to the statically-unrolled causal/banded
    # block-skipping variant (true causal FLOPs; fwd-only steps).
    import contextlib
    from repro.models.attention import attention_options

    unroll_skip = os.environ.get("REPRO_ATTN_UNROLL_SKIP") == "1"
    attn_ctx = (
        attention_options(unroll=True, skip_masked_blocks=True)
        if unroll_skip else contextlib.nullcontext()
    )
    if unroll_skip:
        rec["attn_impl"] = "unrolled_causal_skip"
    try:
        with mesh, use_sharding(mesh, policy), attn_ctx:
            p_specs = shd.param_pspecs(model, policy, mesh)
            p_shardings = shd.as_named(p_specs, mesh)
            full_mesh_batch = shape.step == "train"
            tok_sharding = jax.NamedSharding(
                mesh, shd.token_pspec(shape.global_batch, mesh, full_mesh=full_mesh_batch))
            abstract_params = model.abstract_params()

            if shape.step == "train":
                opt_cfg = _opt_cfg(cfg)
                opt_specs = shd.opt_state_pspecs(model, policy, mesh, opt_cfg)
                opt_shardings = shd.as_named(opt_specs, mesh)
                abstract_opt = _abstract_opt_state(model, opt_cfg)
                step_fn = make_train_step(model, opt_cfg)
                batch = {"tokens": jax.ShapeDtypeStruct(
                    (shape.global_batch, shape.seq_len + 1), jnp.int32)}
                jitted = jax.jit(
                    step_fn,
                    in_shardings=(p_shardings, opt_shardings, {"tokens": tok_sharding}),
                    out_shardings=(p_shardings, opt_shardings, None),
                    donate_argnums=(0, 1),
                )
                lowered = jitted.lower(abstract_params, abstract_opt, batch)
                rec["opt_quantized_moments"] = opt_cfg.quantize_moments
            elif shape.step == "prefill":
                step_fn = make_prefill_step(model, max_len=shape.seq_len)
                batch = jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len), jnp.int32)
                cache_specs = shd.cache_pspecs(
                    model.abstract_cache(shape.global_batch, shape.seq_len), mesh)
                cache_shardings = shd.as_named(cache_specs, mesh)
                logits_sharding = jax.NamedSharding(
                    mesh, shd.logits_pspec(cfg, shape.global_batch, mesh))
                jitted = jax.jit(
                    step_fn,
                    in_shardings=(p_shardings, tok_sharding),
                    out_shardings=(logits_sharding, cache_shardings),
                )
                lowered = jitted.lower(abstract_params, batch)
            else:  # decode
                abstract_kv = model.abstract_cache(shape.global_batch, shape.seq_len)
                cache_specs = shd.cache_pspecs(abstract_kv, mesh)
                cache_shardings = shd.as_named(cache_specs, mesh)
                step_fn = make_decode_step(model)
                batch = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
                logits_sharding = jax.NamedSharding(
                    mesh, shd.logits_pspec(cfg, shape.global_batch, mesh))
                jitted = jax.jit(
                    step_fn,
                    in_shardings=(p_shardings, cache_shardings, tok_sharding),
                    out_shardings=(logits_sharding, cache_shardings),
                    donate_argnums=(1,),
                )
                lowered = jitted.lower(abstract_params, abstract_kv, batch)

            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()

            rec["lower_s"] = round(t_lower - t0, 2)
            rec["compile_s"] = round(t_compile - t_lower, 2)

            mem = {}
            try:
                ma = compiled.memory_analysis()
                for field in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "alias_size_in_bytes",
                    "generated_code_size_in_bytes",
                ):
                    if hasattr(ma, field):
                        mem[field] = int(getattr(ma, field))
            except Exception as e:  # pragma: no cover
                mem["error"] = str(e)
            rec["memory_analysis"] = mem
            args_b = mem.get("argument_size_in_bytes", 0)
            temp_b = mem.get("temp_size_in_bytes", 0)
            out_b = mem.get("output_size_in_bytes", 0)
            alias_b = mem.get("alias_size_in_bytes", 0)
            rec["hbm_per_device_bytes"] = args_b + temp_b + max(out_b - alias_b, 0)

            cost = {}
            try:
                ca = compiled.cost_analysis()
                for k in ("flops", "bytes accessed", "transcendentals", "optimal_seconds"):
                    if k in ca:
                        cost[k] = float(ca[k])
            except Exception as e:  # pragma: no cover
                cost["error"] = str(e)
            rec["cost_analysis"] = cost

            try:
                hlo = compiled.as_text()
                rec["collectives"] = collective_bytes(hlo)
                rec["hlo_len"] = len(hlo)
            except Exception as e:  # pragma: no cover
                rec["collectives"] = {"total_bytes": 0, "error": str(e)}

            # Roofline terms.  cost_analysis is post-SPMD (per-device
            # program) BUT counts scan bodies once — compose the honest
            # totals from stub + n_periods x period + tail (costmodel.py).
            try:
                from repro.launch.costmodel import composed_cost

                comp = composed_cost(cfg, shape, mesh, policy,
                                     skip_masked_blocks=unroll_skip)
                rec["composed"] = comp
                flops_dev = comp["totals"]["flops"]
                bytes_hlo = comp["totals"]["bytes"]
                coll_dev = float(comp["totals"]["collective_bytes"])
                rec["cost_source"] = "composed"
            except Exception as e:
                rec["composed_error"] = f"{type(e).__name__}: {e}"
                flops_dev = cost.get("flops", 0.0)
                bytes_hlo = cost.get("bytes accessed", 0.0)
                coll_dev = float(rec["collectives"].get("total_bytes", 0))
                rec["cost_source"] = "entry_only"

            # Memory term: analytic minimal HBM traffic (bytes-accessed is a
            # pre-fusion upper bound — reported, not used for the term).
            from repro.launch.memmodel import analytic_hbm_bytes, roofline_fraction_for

            mem_model = analytic_hbm_bytes(
                cfg, shape, mesh, opt_quantized=rec.get("opt_quantized_moments", False)
            )
            rec["hbm_traffic_model"] = mem_model
            rec["hlo_bytes_accessed_upper_bound"] = bytes_hlo
            rec["roofline"] = roofline_terms(flops_dev, mem_model["total"], coll_dev)

            tokens = shape.global_batch * (shape.seq_len if shape.step != "decode" else 1)
            model_flops = cfg.model_flops_per_token() * tokens
            if shape.step != "train":
                model_flops /= 3.0  # fwd only: 2N per token instead of 6N
            rec["model_flops_total"] = model_flops
            rec["model_flops_per_device"] = model_flops / n_dev
            rec["useful_flops_ratio"] = (
                (model_flops / n_dev) / flops_dev if flops_dev else 0.0
            )
            # Step-aware roofline score (decode's useful work is streaming).
            rec["roofline"].update(
                roofline_fraction_for(
                    shape.step,
                    rec["roofline"]["t_compute_s"],
                    rec["roofline"]["t_memory_s"],
                    rec["roofline"]["t_collective_s"],
                    useful_flops_frac=min(rec["useful_flops_ratio"], 1.0) or 1.0,
                )
            )
            rec["status"] = "ok"
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 2)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(rec, indent=2))
    return rec


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", action="append", help="arch id (repeatable; default all)")
    ap.add_argument("--shape", action="append", help="shape name (repeatable; default all)")
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="both")
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    ap.add_argument("--list", action="store_true", help="list cells and exit")
    args = ap.parse_args()

    archs = args.arch or list(ARCHS)
    shapes = args.shape or list(SHAPES)
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]

    cells = [(a, s, m) for a in archs for s in shapes for m in meshes]
    if args.list:
        for c in cells:
            print(*c)
        return

    failures = 0
    for arch, shape_name, mesh_kind in cells:
        rec = run_cell(arch, shape_name, mesh_kind, force=args.force)
        status = rec.get("status")
        if status == "ok":
            rt = rec["roofline"]
            print(
                f"[ok]   {arch:26s} {shape_name:12s} {mesh_kind:8s} "
                f"compile={rec.get('compile_s', 0):7.1f}s "
                f"hbm/dev={rec.get('hbm_per_device_bytes', 0)/2**30:7.2f}GiB "
                f"bound={rt['bound']:<10s} frac={rt['roofline_fraction']:.3f}",
                flush=True,
            )
        elif status == "skipped":
            print(f"[skip] {arch:26s} {shape_name:12s} {mesh_kind:8s} {rec['reason']}", flush=True)
        else:
            failures += 1
            print(f"[FAIL] {arch:26s} {shape_name:12s} {mesh_kind:8s} {rec.get('error')}", flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
