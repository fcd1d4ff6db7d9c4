#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

  python3 chipbench/run.py --workload chatglm3-6b.fewshot_batch \
      --seed 7 --seconds 30 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/traffic/<traffic>.json``).  The run draws the weights and the
requests from ``--seed``, warms up every shape the traffic uses, serves
through ``repro.serve.Server`` for ``--seconds``, as the traffic kind
(``chipbench/traffic/<kind>.py``) sends them, then checks a sample of the
served tokens against the plain reference.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the end-to-end metrics, or with ``--trace 1`` the per-layer
ones), ``device`` and, traced, ``breakdown``; its last key, ``checks``,
holds each number compared beside its limit, as do the last lines of
standard error.  No TPU, an unknown device kind, or another number of chips
than the cell asks for: a non-zero exit and no result.

``--control 1`` puts the float8 control in the program's place for the
comparison (``benchlib/check.py``): the run then has to come out not
correct.  The benchmark's own runs never pass it.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(HERE), str(ROOT / "src")]

from benchlib import cells as cells_mod  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache`` at the checkout's root (a fixed path,
    so every run of a checkout finds what its first run compiled)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices_for(chips: int, require_tpu: bool = True):
    """The chips this cell runs on; exits non-zero when they are not
    there."""
    import jax
    from benchlib.peaks import peaks_for
    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        raise SystemExit(f"chipbench: no TPU (JAX platform {d0.platform!r})")
    if len(devs) != chips:
        raise SystemExit(f"chipbench: cell needs {chips} chip(s), JAX sees "
                         f"{len(devs)}")
    peaks = peaks_for(d0.device_kind) if require_tpu else None
    return devs, peaks


def program_model(config: dict):
    """The program's model for a configuration file, checked against the
    published sizes there."""
    from repro.configs.catalog import get_config
    from repro.models import build_model
    prog = config["program"]
    cfg = get_config(prog["arch"])
    cfg = dataclasses.replace(cfg, **prog.get("overrides", {}))
    a = config["arch"]
    got = {"layers": cfg.num_layers, "d_model": cfg.d_model,
           "heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
           "head_dim": cfg.resolved_head_dim, "d_ff": cfg.d_ff,
           "vocab": cfg.vocab_size, "rope_theta": cfg.rope_theta,
           "rope_dims": int(cfg.resolved_head_dim * cfg.rope_fraction),
           "dtype": cfg.dtype}
    want = {k: a[k] for k in got}
    if got != want:
        raise SystemExit(f"chipbench: program config {prog['arch']} is "
                         f"{got}, the configuration file states {want}")
    return build_model(cfg)


def percentile(xs, q):
    import numpy as np
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else 0.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compare the float8 control in the program's place")
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("chipbench: --seed must be >= 0")
    cell = cells_mod.load_cell(args.workload)
    return run_cell(cell, args)


def run_cell(cell, args, *, require_tpu: bool = True) -> int:
    """One run of ``cell``.  Tests pass ``require_tpu=False`` to run it on
    the CPU, without the compile cache."""
    cache_dir = enable_compile_cache() if require_tpu else None
    import jax
    import numpy as np
    from jax import monitoring
    from benchlib import check, serving, weights as weights_mod
    from benchlib.flops import DenseDims
    from benchlib.window import Run

    devs, peaks = devices_for(cell.chips, require_tpu)
    d0 = devs[0]
    log(f"[device] platform {d0.platform} kind {d0.device_kind} count "
        f"{len(devs)}; compile cache {cache_dir}")
    compiles = {"n": 0}

    def on_event(event):
        if event in ("/jax/core/compile/backend_compile_duration",
                     "/jax/compilation_cache/cache_hits"):
            compiles["n"] += 1
    monitoring.register_event_listener(lambda e, **kw: on_event(e))
    monitoring.register_event_duration_secs_listener(
        lambda e, s, **kw: on_event(e))

    config, traffic = cell.config, cell.traffic
    arch = config["arch"]
    sv = dict(config["serve"])
    model = program_model(config)
    weights_mod.check_layout(model.abstract(), arch)
    spec = sv.pop("mesh", None)
    mesh = shardings = None
    if spec:
        from repro.distributed import sharding as sh
        from repro.launch.mesh import build_mesh
        mesh = build_mesh(spec, devices=devs)
        shardings = sh.param_shardings(mesh, sh.serving_rules(mesh),
                                       model.template)
    t = time.monotonic()
    params = weights_mod.make_weights(model.abstract(), args.seed,
                                      arch["dtype"], shardings)
    jax.block_until_ready(params)
    log(f"[setup] weights {time.monotonic() - t:.3f} s")

    from repro.serve import Engine, Server, ServeConfig
    engine = Engine(model, params, ServeConfig(
        **sv, mesh=mesh, seed=args.seed % (2 ** 31)))
    gen = cells_mod.traffic_generator(traffic)
    shortest, longest, total = gen.shapes(traffic)
    plan, buckets, widths = serving.warmup_plan(
        shortest, longest, total, sv["decode_chunk"])
    warm_prompts = gen.cached_prompts(traffic, args.seed, arch["vocab"])
    t = time.monotonic()
    serving.warm(engine, plan, arch["vocab"],
                 np.random.default_rng([args.seed, 9]), warm_prompts)
    log(f"[setup] warm-up {time.monotonic() - t:.3f} s: (prompt, new "
        f"tokens) {plan}, admission buckets {buckets}, decode widths "
        f"{widths}")

    server = Server(engine)
    client = serving.Client(
        server, engine, gen.requests(traffic, args.seed, arch["vocab"]),
        seconds=args.seconds, seed=args.seed)
    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-")
        jax.profiler.start_trace(trace_dir)
    t_trace_start = time.monotonic()
    st0 = serving.counters(engine.stats())
    n_compiles0 = compiles["n"]
    setup_s = time.monotonic() - T_START
    gen.drive(client, traffic)
    time.sleep(max(client.t_end - time.monotonic(), 0.0))
    st1 = serving.counters(engine.stats())
    window_compiles = compiles["n"] - n_compiles0
    t_trace_end = time.monotonic()
    if args.trace:
        jax.profiler.stop_trace()
    t = time.monotonic()
    client.close()
    log(f"[window] stop after the window {time.monotonic() - t:.3f} s")
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devs)
    records = client.records
    attempted = len(records)
    failed = sum(1 for r in records if r.error is not None)
    late = client.lateness
    dims = DenseDims(layers=arch["layers"], d_model=arch["d_model"],
                     heads=arch["heads"], kv_heads=arch["kv_heads"],
                     head_dim=arch["head_dim"], d_ff=arch["d_ff"],
                     vocab=arch["vocab"])
    run = Run(cell=cell, dims=dims, peaks=peaks, chips=len(devs),
              seconds=args.seconds, setup_s=setup_s, t0=client.t0, st0=st0,
              st1=st1, records=records, warm_prompts=warm_prompts)
    log(f"[window] {args.seconds} s: requests due {attempted}, completed "
        f"{sum(1 for r in records if r.result is not None)}, failed "
        f"{failed}; compiles in window {window_compiles}; generator "
        f"lateness max {max(late, default=0.0):.6f} s p99 "
        f"{percentile(late, 99):.6f} s over {len(late)} timed sends; "
        f"admissions {st1['admissions'] - st0['admissions']}, admission "
        f"prefills {st1['admission_prefills'] - st0['admission_prefills']}, "
        f"preemptions {st1['preemptions'] - st0['preemptions']}")
    log(f"[memory] peak_bytes_in_use {memory_peak} on the fullest of "
        f"{len(devs)} device(s)")

    # -- correctness: the engine goes, the reference reads the weights ----
    picked = check.sample(records, args.seed, config["check"]["min_tokens"],
                          config["check"]["min_requests"])
    del server, engine, client
    gc.collect()
    t = time.monotonic()
    stream_ok = all(r.tokens == list(r.result.tokens) for r in picked)
    nums = check.compare(params, arch, picked, control=bool(args.control))
    limit = config["check"]["token_gap_limit"]
    gap = nums["control_gap" if args.control else "token_gap"]
    correct = bool(picked) and stream_ok and failed == 0 and gap <= limit
    log(f"[check] reference {time.monotonic() - t:.3f} s over "
        f"{len(picked)} requests, {nums['tokens_compared']} served tokens; "
        f"stream matches result: {stream_ok}; program token_gap "
        f"{nums['token_gap']}")

    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": int(memory_peak)}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.trace:
        from benchlib import trace as trace_mod
        run.trace = trace_mod.reduce_dir(trace_dir,
                                         t_trace_end - t_trace_start)
        shutil.rmtree(trace_dir, ignore_errors=True)
        entries, kind = cell.per_layer, "metrics"
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    else:
        entries, kind = cell.end_to_end, "e2e"
    readers = cells_mod.metric_readers(entries, kind)
    metrics = {}
    for m in entries:
        v = readers[m["name"]].read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if args.trace:
        result["breakdown"] = run.trace.breakdown()
    checks = {"token_gap": {"value": gap, "limit": limit},
              "failed": {"value": failed, "limit": 0},
              "stream_mismatch": {"value": int(not stream_ok), "limit": 0}}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
