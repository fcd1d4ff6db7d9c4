"""Serving driver: continuous-batching generation with the Engine.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m --reduced \
      --prompts "1,2,3;4,5,6,7,8" --max-new 16

Ragged prompt lengths are handled natively (left-pad + masking); more
prompts than ``--max-batch`` are served in waves over the fixed slot pool.
``--mesh data=4,model=2`` (or ``--mesh auto``) shards params/KV-cache/batch
over a device mesh — token-for-token identical to the single-device run:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \
      --mesh data=4,model=2 --stats

``--server`` runs the same prompts through the long-lived streaming
front-end instead of one batched call: requests are submitted from the
caller thread into a :class:`repro.serve.Server`, tokens print as they
become host-visible, and ``--stats`` then includes per-request TTFT /
tok-per-s percentiles.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs.catalog import get_config
from repro.core import tuning_db
from repro.core.hardware import find_profile, resolve_hardware
from repro.core.registry import GLOBAL_REGISTRY
from repro.launch.common import (add_common_args, add_serving_args,
                                 apply_latency_hiding_flags,
                                 enable_compile_cache)
from repro.models import build_model
from repro.serve import Engine, Request, ServeConfig, Server


def _serve_streaming(eng, prompts, max_new):
    """--server mode: long-lived Server + per-token streaming prints."""
    streams = {i: [] for i in range(len(prompts))}

    def stream_for(i):
        def cb(ev):
            if ev.token is not None:
                streams[i].append(ev.token)
                print(f"[stream] prompt {i} token[{ev.index}] = {ev.token}")
            else:
                print(f"[stream] prompt {i} finished ({ev.finish_reason})")
        return cb

    with Server(eng) as srv:
        handles = [srv.submit(Request(prompt=p, max_new_tokens=max_new,
                                      stream=stream_for(i)))
                   for i, p in enumerate(prompts)]
        results = [h.result(timeout=600) for h in handles]
    for i, (p, res) in enumerate(zip(prompts, results)):
        assert res.tokens == streams[i]   # streamed == batch, by contract
        print(f"prompt={p} -> {res.tokens} "
              f"(ttft {res.ttft_s * 1e3:.1f} ms, {res.tok_per_s:.0f} tok/s"
              + (f", prefix hit: {res.prefix_hit}" if res.prefix_hit
                 else "") + ")")
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--prompts", default="1,2,3;7,8,9",
                    help="';'-separated comma-token prompts")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=None,
                    help="KV-cache slots (default: number of prompts)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--attn-impl", choices=["chunked", "flash"], default=None,
                    help="override the config's attention implementation "
                         "(flash = tuned Pallas kernel for prefill)")
    ap.add_argument("--server", action="store_true",
                    help="serve through the long-lived streaming Server "
                         "(per-token callbacks + TTFT percentiles) instead "
                         "of one batched generate call")
    add_serving_args(ap)
    add_common_args(ap)
    args = ap.parse_args()

    if args.mesh:
        # before the first device touch: the runtime reads its flags once
        print(f"[flags] {apply_latency_hiding_flags(args.hardware)}")
    print(f"[cache] compile cache at {enable_compile_cache()}")
    hardware = resolve_hardware(args.hardware)
    prof = find_profile(hardware)
    print(f"[hw] profile={hardware} "
          f"platform={prof.platform if prof else 'unknown'} "
          f"({'flag' if args.hardware else 'detected'})")
    mesh = rules = None
    if args.mesh:
        from repro.distributed.sharding import serving_rules
        from repro.launch.mesh import build_mesh, describe_mesh
        mesh = build_mesh(args.mesh)
        rules = serving_rules(mesh)
        print(f"[mesh] {describe_mesh(mesh)}")

    loaded = tuning_db.load_all(GLOBAL_REGISTRY, args.tuned_dir)
    for path, count in loaded.items():
        print(f"[tuned] {count} configs from {path}")
    if not loaded:
        print("[tuned] no tuning DB found; using built-in default tiles")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.attn_impl:
        import dataclasses
        cfg = dataclasses.replace(cfg, attention_impl=args.attn_impl)
    model = build_model(cfg)
    # On a mesh every shard is initialised on its own device, by the same
    # inference rules the engine serves with (no full copy on device 0).
    params = model.init(jax.random.PRNGKey(0), mesh=mesh, rules=rules)

    prompts = [[int(t) % cfg.vocab_size for t in p.split(",")]
               for p in args.prompts.split(";")]
    extra = {}
    for k, sds in model.extra_inputs(len(prompts)).items():
        extra[k] = jnp.zeros(sds.shape, sds.dtype)

    eng = Engine(model, params,
                 ServeConfig(max_batch=args.max_batch or len(prompts),
                             temperature=args.temperature,
                             profile=args.stats,
                             hardware=hardware,
                             mesh=mesh,
                             scheduler=args.scheduler,
                             page_size=args.page_size,
                             capacity_tokens=args.capacity_tokens,
                             decode_chunk=args.decode_chunk,
                             prefix_cache=not args.no_prefix_cache))
    from repro.profiling import trace
    if args.server:
        if extra:
            ap.error("--server cannot carry extra-input models "
                     "(extras are positional per drain)")
        with trace(args.trace_dir, enabled=bool(args.trace_dir)) as session:
            _serve_streaming(eng, prompts, args.max_new)
    else:
        with trace(args.trace_dir, enabled=bool(args.trace_dir)) as session:
            outs = eng.generate(prompts, args.max_new,
                                extra_inputs=extra or None)
        for p, o in zip(prompts, outs):
            print(f"prompt={p} -> {o}")
    if session.enabled:
        print(f"[trace] captured {len(session.trace_files())} trace file(s) "
              f"under {args.trace_dir}")

    if args.stats:
        st = eng.stats()
        toks = st["tokens_generated"]
        dec_s = st["decode_seconds"] or 1e-9
        sched = st["scheduler"]
        unit = (f"{int(st['chunks'])} chunk(s)" if sched == "continuous"
                else f"{int(st['waves'])} wave(s)")
        forced = (f" (forced: {st['scheduler_forced']})"
                  if st.get("scheduler_forced") else "")
        print(f"[stats] hw={st['hardware']} ({st['hardware_platform']}), "
              f"scheduler={sched}{forced}, {int(toks)} tokens, {unit}, "
              f"{int(st['device_transfers'])} host transfer(s), "
              f"decode {toks / dec_s:.0f} tok/s")
        if sched == "continuous":
            pages = st.get("pages") or {}
            print(f"[stats] paged KV: page_size={st['page_size']} "
                  f"({st['page_size_source']}), "
                  f"capacity={st['capacity_tokens']} tokens, high water "
                  f"{pages.get('high_water_pages', 0)}/"
                  f"{pages.get('usable_pages', 0)} pages, "
                  f"admissions={st['admissions']} "
                  f"evictions={st['evictions']} "
                  f"preemptions={st['preemptions']}")
        pc = st["prefix_cache"]
        if pc["enabled"]:
            print(f"[stats] prefix cache: {pc['hits_full']} full / "
                  f"{pc['hits_partial']} partial hit(s), {pc['misses']} "
                  f"miss(es), {pc['prefill_tokens_saved']} prefill "
                  f"token(s) saved, {pc['pinned_pages']} page(s) pinned")
        lat = st["latency"]
        if lat["count"]:
            print(f"[stats] latency over {lat['count']} request(s): "
                  f"ttft p50 {lat['ttft_s']['p50'] * 1e3:.1f} ms / "
                  f"p99 {lat['ttft_s']['p99'] * 1e3:.1f} ms, "
                  f"tok/s p50 {lat['tok_per_s']['p50']:.0f}")
        print(f"[stats] mesh={st['mesh']}")
        if st["sharding"]:
            print(f"[stats] sharding rules={st['sharding']['rules']} "
                  f"params={st['sharding']['params']}")
        for shape, info in (st["decode_tile_lookups"] or {}).items():
            local = (f" local={info['local_shape']}"
                     if "local_shape" in info else "")
            print(f"[tiles] decode GEMM {shape:>16s} -> {info['tile']} "
                  f"({info['source']}){local}")
        for shape, info in (st["prefill_flash_lookups"] or {}).items():
            print(f"[tiles] prefill flash {shape:>14s} -> {info['tile']} "
                  f"({info['source']})")


if __name__ == "__main__":
    main()
