"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes and record memory/cost/collective analysis.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                  # all cells, both meshes
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --list

Every record lands incrementally in results/dryrun.json; SKIP rows are
emitted for long_500k on pure full-attention archs (DESIGN.md §4).
"""
import argparse
import dataclasses
import json
import os
import time
import traceback
from typing import Optional

import jax

from repro.configs.base import SHAPES, LONG_500K, ModelConfig, ShapeSpec
from repro.configs.catalog import ARCHITECTURES, get_config
from repro.distributed import sharding as sh
from repro.launch import specs as specs_mod
from repro.launch.hlo_analysis import collective_bytes, op_histogram
from repro.launch.hlo_stats import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.models.model import active_param_count, build_model
from repro.optim.adamw import AdamW
from repro.train import trainer as tr

RESULTS_DEFAULT = "results/dryrun.json"


def lower_cell(cfg: ModelConfig, shape: ShapeSpec, mesh,
               rules: Optional[sh.ShardingRules] = None,
               logit_chunk: Optional[int] = None,
               attn_p_dtype: Optional[str] = None,
               bf16_partials: bool = False,
               remat_policy: Optional[str] = None,
               kv_quant: bool = False):
    """Build + lower the cell's step function. Returns (lowered, meta)."""
    if logit_chunk is not None:
        cfg = dataclasses.replace(cfg, logit_chunk=logit_chunk)
    if attn_p_dtype is not None:
        cfg = dataclasses.replace(cfg, attn_p_dtype=attn_p_dtype)
    if remat_policy is not None:
        cfg = dataclasses.replace(cfg, remat_policy=remat_policy)
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant=True)
    model = build_model(cfg)
    rules = rules or sh.rules_for_mesh(mesh)
    kind, specs = specs_mod.input_specs(cfg, shape)

    from repro.core.gemm_api import execution_context
    from repro.distributed.ctx import activation_policy
    with mesh, activation_policy(mesh, rules), \
            execution_context(bf16_partials=bf16_partials):
        if kind == "train":
            optimizer = AdamW(learning_rate=1e-4)
            state_abs = tr.abstract_train_state(model, optimizer)
            state_shard = tr.state_shardings(mesh, rules, model)
            batch_shard = sh.batch_shardings(mesh, rules, specs["batch"])
            step = tr.make_train_step(model, optimizer)
            jitted = jax.jit(step,
                             in_shardings=(state_shard, batch_shard),
                             out_shardings=(state_shard, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_abs, specs["batch"])
        elif kind == "prefill":
            pshard = sh.param_shardings(mesh, rules, model.template)
            batch_shard = sh.batch_shardings(mesh, rules, specs["batch"])
            cache_shard = sh.cache_shardings(mesh, rules, specs["cache"])
            step = tr.make_prefill_step(model)
            jitted = jax.jit(step,
                             in_shardings=(pshard, batch_shard, cache_shard),
                             out_shardings=(None, cache_shard),
                             donate_argnums=(2,))
            lowered = jitted.lower(model.abstract(), specs["batch"], specs["cache"])
        else:  # decode
            pshard = sh.param_shardings(mesh, rules, model.template)
            tok_shard = sh.batch_shardings(mesh, rules, {"t": specs["tokens"]})["t"]
            cache_shard = sh.cache_shardings(mesh, rules, specs["cache"])
            from jax.sharding import NamedSharding, PartitionSpec as P
            rep = NamedSharding(mesh, P())
            step = tr.make_decode_step(model)
            jitted = jax.jit(step,
                             in_shardings=(pshard, tok_shard, cache_shard, rep),
                             out_shardings=(None, cache_shard),
                             donate_argnums=(2,))
            lowered = jitted.lower(model.abstract(), specs["tokens"],
                                   specs["cache"], specs["offset"])

    n_active = active_param_count(model)
    n_total = model.param_count()
    if kind == "train":
        model_flops = 6 * n_active * shape.tokens
    elif kind == "prefill":
        model_flops = 2 * n_active * shape.tokens
    else:
        model_flops = 2 * n_active * shape.global_batch
    meta = {"kind": kind, "params_total": n_total, "params_active": n_active,
            "model_flops": model_flops}
    return lowered, meta


def run_cell(arch: str, shape_name: str, mesh_name: str,
             logit_chunk: Optional[int] = None, fsdp: bool = True,
             keep_hlo: bool = False, sequence_parallel: bool = False,
             attn_p_dtype: Optional[str] = None,
             bf16_partials: bool = False,
             remat_policy: Optional[str] = None,
             kv_quant: bool = False) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]

    if shape_name == "long_500k" and not cfg.supports_long_context:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "SKIP",
                "reason": "pure full-attention arch: 524k dense-attention "
                          "decode is out of operating envelope (DESIGN.md §4)"}

    multi = mesh_name == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    rules = sh.rules_for_mesh(mesh, fsdp=fsdp,
                              sequence_parallel=sequence_parallel)
    t0 = time.time()
    lowered, meta = lower_cell(cfg, shape, mesh, rules,
                               logit_chunk=logit_chunk,
                               attn_p_dtype=attn_p_dtype,
                               bf16_partials=bf16_partials,
                               remat_policy=remat_policy,
                               kv_quant=kv_quant)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    try:
        mem = compiled.memory_analysis()
        mem_rec = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not implement everything
        mem_rec = {"error": str(e)}

    try:
        cost = compiled.cost_analysis()
        cost_rec = {"flops": float(cost.get("flops", -1)),
                    "bytes_accessed": float(cost.get("bytes accessed", -1))}
    except Exception as e:
        cost_rec = {"error": str(e)}

    hlo = compiled.as_text()
    coll = collective_bytes(hlo)            # raw (uncorrected) sums
    hist = op_histogram(hlo)
    stats = analyze_hlo(hlo, default_group=16)  # trip-count-corrected

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "OK", "chips": mesh.devices.size,
        "seconds_lower": round(t_lower, 1),
        "seconds_compile": round(t_compile, 1),
        "memory": mem_rec, "cost": cost_rec,
        "collectives": coll, "op_histogram": hist,
        "hlo_stats": {
            "flops": stats.flops,
            "traffic_bytes": stats.traffic_bytes,
            "collective_result_bytes": stats.collective_result_bytes,
            "collective_link_bytes": stats.collective_link_bytes,
            "collective_count": stats.collective_count,
            "dot_count": stats.dot_count,
            "while_trips": stats.while_trips,
            "top_collectives": stats.top_collectives,
        },
        "hlo_chars": len(hlo),
        **meta,
    }
    if keep_hlo:
        rec["hlo_text"] = hlo
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="shape name (default: all)")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=RESULTS_DEFAULT)
    ap.add_argument("--logit-chunk", type=int, default=None)
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--attn-p-dtype", default=None,
                    help="e.g. bfloat16 (halves the attention P buffer)")
    ap.add_argument("--bf16-partials", action="store_true",
                    help="bf16 cross-shard matmul reductions")
    ap.add_argument("--remat-policy", default=None, choices=["full", "dots"])
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache (decode memory-term optimization)")
    ap.add_argument("--tag", default=None,
                    help="suffix results key with #<tag> (perf iterations)")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--force", action="store_true", help="recompute existing")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()
    # 512 host devices for the 16x16 single-pod and 2x16x16 multi-pod
    # production meshes.  Set before the first device touch (jax reads
    # XLA_FLAGS once, at backend init), and only in this process.
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS", ""),
        "--xla_force_host_platform_device_count=512"]))

    archs = [args.arch] if args.arch else list(ARCHITECTURES)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.list:
        for a in archs:
            for s in shapes:
                print(f"{a} x {s}")
        return

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    for a in archs:
        for s in shapes:
            for m in meshes:
                key = f"{a}/{s}/{m}"
                if args.tag:
                    key += f"#{args.tag}"
                if key in results and results[key].get("status") in ("OK", "SKIP") \
                        and not args.force:
                    print(f"[cached] {key}")
                    continue
                print(f"[run] {key} ...", flush=True)
                try:
                    rec = run_cell(a, s, m, logit_chunk=args.logit_chunk,
                                   fsdp=not args.no_fsdp,
                                   sequence_parallel=args.seq_parallel,
                                   attn_p_dtype=args.attn_p_dtype,
                                   bf16_partials=args.bf16_partials,
                                   remat_policy=args.remat_policy,
                                   kv_quant=args.kv_quant)
                    if args.tag:
                        rec["tag"] = args.tag
                except Exception as e:
                    rec = {"arch": a, "shape": s, "mesh": m,
                           "status": "FAIL", "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                results[key] = rec
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
                status = rec["status"]
                extra = ""
                if status == "OK":
                    extra = (f" flops/dev={rec['cost'].get('flops', 0):.3g}"
                             f" coll={rec['collectives']['total']:.3g}B"
                             f" compile={rec['seconds_compile']}s")
                print(f"[{status}] {key}{extra}", flush=True)

    ok = sum(1 for r in results.values() if r["status"] == "OK")
    skip = sum(1 for r in results.values() if r["status"] == "SKIP")
    fail = sum(1 for r in results.values() if r["status"] == "FAIL")
    print(f"\nTotal: {ok} OK, {skip} SKIP, {fail} FAIL / {len(results)}")


if __name__ == "__main__":
    main()
