#!/usr/bin/env python
"""Profile the serve/train paths and render where the time goes vs could go.

    python scripts/profile.py serve --mesh data=4,model=2
    python scripts/profile.py train --steps 3
    python scripts/profile.py diff PROFILE_serving.json PROFILE_other.json

``serve``/``train`` run a reduced workload twice — a warmup pass compiles
everything OUTSIDE the trace, then the measured pass runs under
``repro.profiling.trace`` — post-process the capture into the per-op-family
breakdown (collective vs GEMM vs attention vs host-transfer device time,
host-sync counts, ``serve.*``/``train.*`` annotation spans), attach the
analytic roofline of the same step (HLO-derived compute/memory/collective
terms against the hardware profile's peaks), and write a schema-valid
``PROFILE_<kind>.json``.  The report prints both side by side: the measured
breakdown is "where the time goes", the roofline is "where it could go".

``--mesh data=N,model=M`` forces the host to expose enough devices (the
XLA flag must precede jax's first init, which is why this script sets it
before importing jax).  ``diff`` compares two PROFILE files family by
family — e.g. the same serve workload before/after a sharding change.

The CI profiling leg runs ``serve --mesh data=4,model=2`` and fails on any
schema violation (``validate_profile``) — op families missing, zero totals,
or a trace that captured nothing.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))


def _mesh_devices(spec):
    """Device count a --mesh spec needs (None for no/auto mesh) — computed
    WITHOUT importing jax/repro so the device-count flag can still be set."""
    if not spec or spec.strip() == "auto":
        return None
    n = 1
    for part in spec.split(","):
        part = part.strip()
        if "=" in part:
            n *= int(part.partition("=")[2])
    return n


def _ensure_devices(n: int) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = " ".join(filter(None, [
            flags, f"--xla_force_host_platform_device_count={n}"]))


# ---------------------------------------------------------------------------
# Roofline of the measured step (the "where it could go" column)
# ---------------------------------------------------------------------------

def _roofline(lowered_fn, args_, kind, arch, mesh, model_flops, hardware):
    """Lower+compile the step, run the trip-count-corrected HLO analyzer,
    and return the roofline row (None when the profile is unregistered or
    the lowering fails — the roofline is advisory, never fatal)."""
    try:
        import jax
        from repro.core.hardware import get_profile
        from repro.distributed.sharding import mesh_axis_label
        from repro.launch.hlo_stats import analyze_hlo
        from repro.launch.roofline import roofline_row
        chips = int(mesh.size) if mesh is not None else 1
        hlo = jax.jit(lowered_fn).lower(*args_).compile().as_text()
        stats = analyze_hlo(hlo, default_group=chips)
        rec = {
            "status": "OK", "arch": arch, "kind": kind,
            "shape": kind, "mesh": mesh_axis_label(mesh) or "single",
            "chips": chips, "model_flops": model_flops,
            "hlo_stats": {
                "flops": stats.flops,
                "traffic_bytes": stats.traffic_bytes,
                "collective_link_bytes": stats.collective_link_bytes,
                "collective_count": stats.collective_count,
            },
        }
        return roofline_row(rec, get_profile(hardware))
    except Exception as e:      # advisory: report the miss, keep the profile
        print(f"[roofline] skipped: {type(e).__name__}: {e}")
        return None


# ---------------------------------------------------------------------------
# Rendering
# ---------------------------------------------------------------------------

def _fmt_us(us: float) -> str:
    return f"{us / 1e3:.2f}ms" if us >= 1e3 else f"{us:.0f}us"


def render(blob: dict) -> None:
    fams = blob["families"]
    print(f"\n[profile] kind={blob['kind']} hardware={blob['hardware']} "
          f"mesh={blob['mesh'] or 'single'}")
    print(f"[profile] device-op time {_fmt_us(blob['totals']['op_us'])} over "
          f"wall {_fmt_us(blob['totals']['wall_us'])}; "
          f"host syncs: {blob['host_syncs']}")
    print("[profile] family breakdown (device time):")
    for fam, e in fams.items():
        bar = "#" * int(round(e["fraction"] * 40))
        print(f"  {fam:14s} {_fmt_us(e['us']):>10s} {e['fraction']*100:5.1f}% "
              f"(n={e['count']:<5d}) {bar}")
    if blob.get("annotations"):
        print("[profile] annotated spans (wall time):")
        for name, e in blob["annotations"].items():
            print(f"  {name:22s} {_fmt_us(e['us']):>10s} (n={e['count']})")
    top = blob.get("top_ops") or []
    if top:
        ops = ", ".join(f"{o['name']}={_fmt_us(o['us'])}" for o in top[:6])
        print(f"[profile] top ops: {ops}")
    r = blob.get("roofline")
    if r:
        print(f"[roofline] analytic bounds on {r['hardware']} "
              f"({r['chips']} chip(s)): compute {r['compute_s']*1e6:.1f}us | "
              f"memory {r['memory_s']*1e6:.1f}us | "
              f"collective {r['collective_s']*1e6:.1f}us "
              f"-> dominant: {r['dominant']}")
        terms = {"compute": r["compute_s"], "memory": r["memory_s"],
                 "collective": r["collective_s"]}
        total = sum(terms.values()) or 1.0
        meas_coll = fams["collective"]["fraction"]
        print(f"[compare] collective share — measured {meas_coll*100:.1f}% "
              f"vs roofline {terms['collective']/total*100:.1f}%: a large "
              "measured excess means collectives are NOT overlapped "
              "(latency-hiding headroom)")


def _write(blob: dict, out: str) -> None:
    from repro.profiling import validate_profile
    validate_profile(blob)
    with open(out, "w") as f:
        json.dump(blob, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"[profile] wrote {out}")


# ---------------------------------------------------------------------------
# serve | train | diff
# ---------------------------------------------------------------------------

def cmd_serve(args) -> None:
    import jax
    from repro.configs.catalog import get_config
    from repro.core.hardware import resolve_hardware
    from repro.distributed.sharding import mesh_axis_label
    from repro.launch.mesh import build_mesh
    from repro.models import build_model
    from repro.models.model import active_param_count
    from repro.profiling import build_profile, trace
    from repro.serve import Engine, ServeConfig

    hardware = resolve_hardware(args.hardware)
    mesh = build_mesh(args.mesh) if args.mesh else None
    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [[(7 * i + j) % cfg.vocab_size for j in range(args.plen)]
               for i in range(args.batch)]
    eng = Engine(model, params,
                 ServeConfig(max_batch=args.batch, max_len=args.max_len,
                             profile=True, hardware=hardware, mesh=mesh))
    print("[profile] warmup (compile, outside the trace)...")
    eng.generate(prompts, args.max_new)
    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="repro-trace-serve-")
    print(f"[profile] tracing into {trace_dir} ...")
    with trace(trace_dir):
        eng.generate(prompts, args.max_new)

    st = eng.stats()
    # the continuous scheduler keeps KV in pages, never in a dense pool
    cache = (eng._cache if eng._cache is not None
             else model.init_cache(args.batch, args.max_len))
    roof = _roofline(
        eng._with_mesh(model.decode_step),
        (eng.params, jax.numpy.zeros((args.batch, 1), jax.numpy.int32),
         cache, jax.numpy.int32(0),
         jax.numpy.zeros((args.batch,), jax.numpy.int32)),
        "decode", cfg.name, mesh,
        2 * active_param_count(model) * args.batch, hardware)
    blob = build_profile(
        "serving", trace_dir=trace_dir, hardware=hardware,
        mesh=mesh_axis_label(mesh), roofline=roof,
        extra={"engine": {
            "decode_tok_s": (st["tokens_generated"] / st["decode_seconds"]
                             if st["decode_seconds"] else 0.0),
            "device_transfers": st["device_transfers"],
            "waves": st["waves"],
            "decode_unroll": st["decode_unroll"],
            "decode_unroll_source": st["decode_unroll_source"],
        }})
    _write(blob, args.out)
    render(blob)


def cmd_train(args) -> None:
    import jax
    from repro.configs.catalog import get_config
    from repro.core.hardware import resolve_hardware
    from repro.data import DataConfig, TokenPipeline
    from repro.distributed import sharding as sh
    from repro.distributed.sharding import mesh_axis_label
    from repro.launch.mesh import build_mesh
    from repro.models import build_model
    from repro.models.model import active_param_count
    from repro.optim import AdamW
    from repro.profiling import annotate, build_profile, trace
    from repro.train import Trainer, TrainerConfig, init_train_state

    hardware = resolve_hardware(args.hardware)
    mesh = build_mesh(args.mesh) if args.mesh else None
    rules = sh.rules_for_mesh(mesh) if mesh is not None else None
    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    model = build_model(cfg)
    opt = AdamW(learning_rate=1e-3)
    pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                    seq_len=args.seq_len,
                                    global_batch=args.batch))
    trainer = Trainer(model, opt, pipe,
                      TrainerConfig(total_steps=args.steps + 1, log_every=10),
                      mesh=mesh, rules=rules)
    state = init_train_state(model, opt, jax.random.PRNGKey(0), False)
    print("[profile] warmup step (compile, outside the trace)...")
    state, metrics = trainer._step(state, trainer.data_iter(0))
    jax.block_until_ready(metrics["loss"])
    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="repro-trace-train-")
    print(f"[profile] tracing {args.steps} step(s) into {trace_dir} ...")
    with trace(trace_dir):
        for i in range(1, args.steps + 1):
            with annotate("train.step"):
                state, metrics = trainer._step(state, trainer.data_iter(i))
        jax.block_until_ready(metrics["loss"])

    roof = _roofline(
        lambda s, b: trainer._step(s, b), (state, trainer.data_iter(0)),
        "train", cfg.name, mesh,
        6 * active_param_count(model) * args.batch * args.seq_len, hardware)
    blob = build_profile("training", trace_dir=trace_dir, hardware=hardware,
                         mesh=mesh_axis_label(mesh), roofline=roof,
                         extra={"steps_traced": args.steps})
    _write(blob, args.out)
    render(blob)


def cmd_diff(args) -> None:
    from repro.profiling import FAMILIES, validate_profile
    with open(args.a) as f:
        a = validate_profile(json.load(f))
    with open(args.b) as f:
        b = validate_profile(json.load(f))
    print(f"[diff] A={args.a} (kind={a['kind']}, mesh={a['mesh']}) "
          f"vs B={args.b} (kind={b['kind']}, mesh={b['mesh']})")
    print(f"  {'family':14s} {'A':>10s} {'B':>10s} {'B/A':>7s}")
    for fam in FAMILIES:
        ua, ub = a["families"][fam]["us"], b["families"][fam]["us"]
        ratio = f"{ub / ua:.2f}x" if ua else "-"
        print(f"  {fam:14s} {_fmt_us(ua):>10s} {_fmt_us(ub):>10s} {ratio:>7s}")
    wa, wb = a["totals"]["wall_us"], b["totals"]["wall_us"]
    print(f"  {'wall':14s} {_fmt_us(wa):>10s} {_fmt_us(wb):>10s} "
          f"{(wb / wa if wa else 0):.2f}x")
    print(f"  host syncs: {a['host_syncs']} -> {b['host_syncs']}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)

    def common(p):
        p.add_argument("--arch", default="llama3.2-1b")
        p.add_argument("--full", action="store_true",
                       help="full-size config (default: reduced, CPU-runnable)")
        p.add_argument("--mesh", default=None,
                       help="'data=N,model=M' (forces host device count)")
        p.add_argument("--hardware", default=None)
        p.add_argument("--batch", type=int, default=8)
        p.add_argument("--trace-dir", default=None,
                       help="keep the raw trace here (default: temp dir)")

    ps = sub.add_parser("serve", help="profile a serve-engine generate call")
    common(ps)
    ps.add_argument("--plen", type=int, default=16)
    ps.add_argument("--max-new", type=int, default=16)
    ps.add_argument("--max-len", type=int, default=256)
    ps.add_argument("--out", default="PROFILE_serving.json")

    pt = sub.add_parser("train", help="profile training steps")
    common(pt)
    pt.add_argument("--steps", type=int, default=2)
    pt.add_argument("--seq-len", type=int, default=32)
    pt.add_argument("--out", default="PROFILE_training.json")

    pd = sub.add_parser("diff", help="compare two PROFILE_*.json files")
    pd.add_argument("a")
    pd.add_argument("b")

    args = ap.parse_args()
    if args.cmd in ("serve", "train") and args.mesh:
        from repro.launch.common import apply_latency_hiding_flags
        print(f"[flags] {apply_latency_hiding_flags(args.hardware)}")
        n = _mesh_devices(args.mesh)
        if n and n > 1:
            _ensure_devices(n)
    {"serve": cmd_serve, "train": cmd_train, "diff": cmd_diff}[args.cmd](args)


if __name__ == "__main__":
    main()
