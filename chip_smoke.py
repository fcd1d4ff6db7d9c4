#!/usr/bin/env python3
"""Smoke test of the serving path on TPU v5e chips.

Serves a full-width model with random weights (drawn from ``--seed``)
through ``repro.serve.Engine``: the Pallas GEMM and flash-attention kernels
compiled by Mosaic, the continuous scheduler, the paged KV cache and the
prefix cache.  It then judges what was served by logits, against one plain
float32 forward of the same weights (XLA matmuls, chunked attention,
highest matmul precision).

  python3 chip_smoke.py               # one chip: llama3.2-1b
  python3 chip_smoke.py --four-chips  # four chips: yi-9b on data=1,model=4

Everything runs in this one process, which holds the chips.  Any failure,
a missing TPU included, exits non-zero.  On success the last line of
standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import pathlib
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

#: tokens generated per request
NEW_TOKENS = 32

#: All tolerances are in units of the reference logits' standard deviation
#: over the vocabulary, per position.  The engine keeps weights, activations
#: and the KV cache in bf16 (f32 accumulation inside each kernel); the
#: reference runs the same weights in f32.  bf16 keeps 8 significand bits, so
#: each of the dozen roundings per layer is off by up to 2^-9 relative; over
#: 16-48 layers they add up to an RMS error of a few percent of the spread
#: (0.013-0.020 measured on a 16-layer, d_model 256 bf16 copy on the CPU).  A
#: wrong mask or shard layout moves logits by about the spread itself.
PREFILL_RMS_TOL = 0.05
#: The largest error over a 64k-128k vocabulary sits about 4.5 RMS errors
#: out for Gaussian noise; a wrong tile edge moves a block of 128 logits by
#: about the spread, which this catches where the RMS hardly moves.
PREFILL_MAX_TOL = 0.25
#: A served token is the argmax of the engine's logits, so its reference
#: logit trails the reference maximum only by the errors of two logits,
#: each a typical one (PREFILL_RMS_TOL); 0.25 leaves room for 2.5 times
#: that limit on both, plus the drift of 32 decode steps over a bf16 cache.
TOKEN_GAP_TOL = 0.25

#: (config, mesh spec, first batch's prompt lengths, shared prefix length,
#: tail of the request that shares it).  The shared prefix is page aligned
#: for every power-of-two page size up to 128 tokens.  Every prompt plus its
#: new tokens stays inside one power-of-two bucket (512 on one chip, 256 on
#: four), so admission and the decode chunk compile once each.
ONE_CHIP = ("llama3.2-1b", None, (16, 57, 130, 250, 400), 256, 100)
FOUR_CHIPS = ("yi-9b", "data=1,model=4", (20, 130, 200), 128, 60)


def make_prompts(rng, vocab: int, lengths, shared: int, tail: int):
    """Two batches: random prompts of ``lengths``, then one request whose
    first ``shared`` tokens are those of the longest prompt."""
    first = [rng.integers(0, vocab, n).tolist() for n in lengths]
    longest = max(first, key=len)
    second = [longest[:shared] + rng.integers(0, vocab, tail).tolist()]
    return first, second


@contextlib.contextmanager
def dump_programs():
    """Write every module JAX hands to the compiler into a scratch dir."""
    import jax
    with tempfile.TemporaryDirectory() as d:
        jax.config.update("jax_dump_ir_to", d)
        try:
            yield pathlib.Path(d)
        finally:
            jax.config.update("jax_dump_ir_to", "")


def kernels_in(dump: pathlib.Path, program: str):
    """Names of the Mosaic kernels (``tpu_custom_call``) in the dumped
    module of the jitted ``program``; fails if it was never compiled."""
    import re
    files = sorted(dump.glob(f"*_jit_{program}_compile.mlir"))
    if not files:
        raise AssertionError(f"program {program!r} was never compiled")
    names = set()
    for f in files:
        text = f.read_text()
        for call in re.finditer(r"tpu_custom_call\(.*", text):
            m = re.search(r'kernel_name = "(\w+)"', call.group(0))
            names.add(m.group(1) if m else "?")
    return sorted(names)


def bytes_in_use(devices):
    """Device memory in use, per device, as the TPU runtime reports it."""
    return [d.memory_stats()["bytes_in_use"] for d in devices]


def served_prefill_logits(eng, prompt):
    """The engine's last-position prefill logits for ``prompt``: the prefix
    cache keeps, for every prefilled prompt, the row its first token was
    sampled from."""
    match = eng._prefix.match(prompt)
    assert match is not None and match.full, "prompt not in the prefix cache"
    return match.entry.logits0


def serve(model, params, *, hardware, mesh, batches, seed):
    """Serve ``batches`` (one ``run()`` each) -> (engine, results, report)."""
    from repro.serve import Engine, Request, ServeConfig
    eng = Engine(model, params, ServeConfig(
        max_batch=8, max_len=512, hardware=hardware, mesh=mesh, seed=seed))
    results, seconds = [], []
    with dump_programs() as dump:
        for prompts in batches:
            t0 = time.perf_counter()
            for p in prompts:
                eng.submit(Request(prompt=p, max_new_tokens=NEW_TOKENS))
            results += eng.run()
            seconds.append(time.perf_counter() - t0)
        kernels = {name: kernels_in(dump, name)
                   for name in ("admit_fn", "chunk_fn")}
    return eng, results, {"run_seconds": seconds, "kernels": kernels}


def reference_scores(model, params, *, hardware, mesh, rules, prompts,
                     served):
    """One float32 forward over prompt + served tokens (right-padded into
    one batch; attention is causal, so padding never reaches a scored
    position).  Returns, per request, the reference logits at the last
    prompt position and, per generated position, how far the served
    token's reference logit trails the reference maximum, in standard
    deviations of that position's logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import execution_context
    from repro.distributed.ctx import activation_policy
    from repro.models import build_model
    ref = build_model(dataclasses.replace(
        model.cfg, dtype="float32", attention_impl="chunked"))
    width = max(len(p) + len(s) for p, s in zip(prompts, served))
    toks = np.zeros((len(prompts), width), np.int32)
    pos = np.zeros((len(prompts), NEW_TOKENS), np.int32)
    for i, (p, s) in enumerate(zip(prompts, served)):
        toks[i, :len(p) + len(s)] = p + s
        pos[i] = len(p) - 1 + np.arange(NEW_TOKENS)

    def score(params, toks, pos, served):
        logits, _ = ref.forward(params, {"tokens": toks})
        at = jnp.take_along_axis(logits, pos[:, :, None], axis=1)
        std = at.std(axis=-1)
        got = jnp.take_along_axis(at, served[:, :, None], axis=-1)[..., 0]
        return at[:, 0], std[:, 0], (at.max(axis=-1) - got) / std

    policy = (activation_policy(mesh, rules) if mesh is not None
              else contextlib.nullcontext())
    with execution_context(backend="xla", hardware=hardware), \
            jax.default_matmul_precision("highest"), policy:
        out = jax.jit(score)(params, jnp.asarray(toks), jnp.asarray(pos),
                             jnp.asarray(np.asarray(served, np.int32)))
    return jax.device_get(out)


def run(cfg, mesh_spec, lengths, shared: int, tail: int, *,
        seed: int, hardware: str):
    """Serve ``cfg`` with flash attention and judge it; returns the lines
    to print."""
    import jax
    import numpy as np
    from repro.distributed.sharding import serving_rules
    from repro.launch.mesh import build_mesh
    from repro.models import build_model

    cfg = dataclasses.replace(cfg, attention_impl="flash")
    model = build_model(cfg)
    mesh = build_mesh(mesh_spec)
    rules = serving_rules(mesh) if mesh is not None else None
    lines = [f"[model] {cfg.name}: {model.param_count() / 1e9:.3f}B params "
             f"{cfg.dtype}, attention {cfg.attention_impl}, mesh {mesh_spec}"]

    t0 = time.perf_counter()
    params = model.init(jax.random.PRNGKey(seed), mesh=mesh, rules=rules)
    jax.block_until_ready(params)
    devices = list(mesh.devices.flat) if mesh is not None else jax.devices()[:1]
    held = bytes_in_use(devices)
    total = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    lines.append(f"[init] {time.perf_counter() - t0:.3f} s, {total} bytes of "
                 f"parameters, bytes in use per device {held}")
    # initialised sharded: no device ever held the whole model
    assert len(devices) == 1 or max(held) < total / 2, (held, total)

    rng = np.random.default_rng(seed)
    batches = make_prompts(rng, cfg.vocab_size, lengths, shared, tail)
    eng, results, report = serve(model, params, hardware=hardware,
                                 mesh=mesh, batches=batches, seed=seed)
    st = eng.stats()
    prompts = [p for b in batches for p in b]
    served = [r.tokens for r in results]
    lines.append(f"[serve] runs {report['run_seconds']} s, "
                 f"{int(st['tokens_generated'])} tokens, prefill "
                 f"{st['prefill_seconds']:.3f} s, decode "
                 f"{st['decode_seconds']:.3f} s, {int(st['chunks'])} chunks")
    lines.append(f"[kernels] {report['kernels']}")

    # -- what the engine did -----------------------------------------------
    assert all(len(s) == NEW_TOKENS for s in served), [len(s) for s in served]
    for name, kernels in report["kernels"].items():
        assert "_gemm_kernel" in kernels, (name, kernels)
    assert "_flash_kernel" in report["kernels"]["admit_fn"], report
    tiers = {}
    for table in ("decode_tile_lookups", "prefill_flash_lookups"):
        assert st[table], f"no {table} recorded"
        for shape, info in st[table].items():
            tiers[info["source"]] = tiers.get(info["source"], 0) + 1
            assert info["source"] != "fallback", (table, shape, info)
    lines.append(f"[tiles] lookup tiers {tiers}, registry "
                 f"{st['registry_hit_stats']}")
    pc = st["prefix_cache"]
    assert shared % st["page_size"] == 0, (shared, st["page_size"])
    assert results[-1].prefix_hit == "partial", results[-1]
    lines.append(f"[prefix] page_size {st['page_size']} "
                 f"({st['page_size_source']}), {pc['hits_partial']} partial "
                 f"hit(s), {pc['prefill_tokens_saved']} tokens saved")

    # -- judged by logits ----------------------------------------------------
    t0 = time.perf_counter()
    ref_last, ref_std, gaps = reference_scores(
        model, params, hardware=hardware, mesh=mesh, rules=rules,
        prompts=prompts, served=served)
    lines.append(f"[reference] {time.perf_counter() - t0:.3f} s")
    eng_last = np.stack([np.asarray(jax.device_get(
        served_prefill_logits(eng, p))) for p in prompts])
    assert np.isfinite(eng_last).all() and np.isfinite(ref_last).all()
    diff = eng_last - ref_last
    prefill_rms = np.sqrt((diff * diff).mean(axis=-1)) / ref_std
    prefill_max = np.abs(diff).max(axis=-1) / ref_std
    lines.append(f"[check] prefill logits rms error / ref std per request "
                 f"{prefill_rms.tolist()} (tol {PREFILL_RMS_TOL}); max error "
                 f"/ ref std {prefill_max.tolist()} (tol {PREFILL_MAX_TOL})")
    lines.append(f"[check] served-token gap / ref std, max per request "
                 f"{gaps.max(axis=-1).tolist()} (tol {TOKEN_GAP_TOL}), "
                 f"argmax agreement {float((gaps == 0).mean())}")
    assert (prefill_rms <= PREFILL_RMS_TOL).all(), prefill_rms
    assert (prefill_max <= PREFILL_MAX_TOL).all(), prefill_max
    assert (gaps <= TOKEN_GAP_TOL).all(), gaps.max(axis=-1)
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve yi-9b on a data=1,model=4 mesh instead")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.common import (apply_latency_hiding_flags,
                                     enable_compile_cache)
    if args.four_chips:
        # the mesh serves with the flags the launchers set for a v5e; they
        # must be in place before the first device touch
        print(f"[flags] {apply_latency_hiding_flags('tpu-v5e')}")
    cache_dir = enable_compile_cache()
    import jax
    from jax import monitoring
    from repro.configs.catalog import get_config
    from repro.core import ExecutionContext
    from repro.core.hardware import detect_hardware, get_profile

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX platform {dev.platform!r})")
    hardware = detect_hardware(devices)       # raises on an unknown kind
    assert get_profile(hardware).platform == "tpu", hardware
    backend = ExecutionContext(hardware=hardware).resolve_backend()
    assert backend == "pallas-tpu", backend
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        raise SystemExit(f"chip_smoke: needs {need} chips, "
                         f"found {len(devices)}")
    print(f"[device] {dev.device_kind} x{len(devices)} -> profile "
          f"{hardware}, backend {backend}, compile cache {cache_dir}")

    compile_s = []
    monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    t0 = time.perf_counter()
    arch, *shape = FOUR_CHIPS if args.four_chips else ONE_CHIP
    lines = run(get_config(arch), *shape, seed=args.seed, hardware=hardware)
    for line in lines:
        print(line)
    print(f"[time] total {time.perf_counter() - t0:.3f} s, of which "
          f"backend compile {sum(compile_s):.3f} s in {len(compile_s)} "
          f"compiles")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
