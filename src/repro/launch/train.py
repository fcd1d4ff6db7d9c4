"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \
      --steps 100 --seq-len 64 --batch 8 --ckpt-dir /tmp/ckpt

On a real cluster this runs once per host under the usual multi-host jax
bootstrap (jax.distributed.initialize); the mesh/rules/elastic-restore logic
is identical.  ``--resume`` restarts from the latest checkpoint (the
fault-tolerance path: deterministic data + atomic checkpoints = exact
replay).  ``--mesh data=N,model=M`` (or ``--mesh auto``) builds a device
mesh when the host exposes multiple devices; the train step is then
jit-sharded — params by the sharding rules, the batch over the data axes.
The retired ``--mesh-data``/``--mesh-model`` pair still parses: it warns
and forwards onto ``--mesh``.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.checkpoint import Checkpointer
from repro.configs.catalog import get_config
from repro.core import execution_context, tuning_db
from repro.core.hardware import resolve_hardware
from repro.core.registry import GLOBAL_REGISTRY
from repro.data import DataConfig, TokenPipeline
from repro.distributed import sharding as sh
from repro.launch.common import (add_common_args,
                                 apply_latency_hiding_flags, deprecated_flag,
                                 enable_compile_cache)
from repro.launch.mesh import build_mesh, describe_mesh
from repro.models import build_model
from repro.optim import AdamW, warmup_cosine
from repro.train import (Trainer, TrainerConfig, abstract_train_state,
                         init_train_state, state_shardings)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-topology config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--step-deadline-s", type=float, default=None)
    add_common_args(ap)
    # retired in favour of the unified --mesh spec; warn + forward
    deprecated_flag(ap, "--mesh-data", "--mesh", type=int)
    deprecated_flag(ap, "--mesh-model", "--mesh", type=int)
    args = ap.parse_args()
    used = getattr(args, "_deprecated_used", set())
    if {"mesh_data", "mesh_model"} & used and not args.mesh:
        data = args.mesh_data or 1
        model_ax = args.mesh_model or 1
        if data * model_ax > 1:
            args.mesh = f"data={data},model={model_ax}"

    if args.mesh:
        # before the first device touch: the runtime reads its flags once
        print(f"[flags] {apply_latency_hiding_flags(args.hardware)}")
    print(f"[cache] compile cache at {enable_compile_cache()}")
    hardware = resolve_hardware(args.hardware)
    print(f"[hw] profile={hardware} "
          f"({'flag' if args.hardware else 'detected'})")

    loaded = tuning_db.load_all(GLOBAL_REGISTRY, args.tuned_dir)
    for path, count in loaded.items():
        print(f"[tuned] {count} configs from {path}")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    print(f"arch={cfg.name} family={cfg.family} "
          f"params={model.param_count() / 1e6:.1f}M")

    opt = AdamW(learning_rate=warmup_cosine(args.lr, 10, args.steps))
    pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                    seq_len=args.seq_len,
                                    global_batch=args.batch))

    mesh = rules = None
    if args.mesh:
        mesh = build_mesh(args.mesh)
        rules = sh.rules_for_mesh(mesh)
        print(f"[mesh] {describe_mesh(mesh)} rules={rules}")

    ck = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    tcfg = TrainerConfig(total_steps=args.steps, log_every=10,
                         checkpoint_every=args.ckpt_every,
                         microbatches=args.microbatches,
                         use_compression=args.compress_grads,
                         step_deadline_s=args.step_deadline_s)
    trainer = Trainer(model, opt, pipe, tcfg, mesh=mesh, rules=rules,
                      checkpointer=ck)

    start = 0
    if args.resume and ck is not None and ck.latest_step() is not None:
        start = ck.latest_step()
        template = abstract_train_state(model, opt, args.compress_grads)
        shardings = (state_shardings(mesh, rules, model, args.compress_grads)
                     if mesh is not None else None)
        state = ck.restore(start, template, shardings)
        print(f"resumed from step {start}")
    else:
        state = init_train_state(model, opt, jax.random.PRNGKey(0),
                                 args.compress_grads)

    from repro.profiling import trace
    t0 = time.perf_counter()
    with execution_context(hardware=hardware), \
            trace(args.trace_dir, enabled=bool(args.trace_dir)):
        state, history = trainer.run(state, start_step=start)
    wall = time.perf_counter() - t0
    for step, loss in history:
        print(f"step {step:6d}  loss {loss:.4f}")
    print(f"done at step {int(state.step)}")
    if args.stats:
        steps_run = max(int(state.step) - start, 1)
        toks = steps_run * args.batch * args.seq_len
        print(f"[stats] hw={hardware}, {steps_run} step(s) in {wall:.1f}s "
              f"({steps_run / wall:.2f} step/s, {toks / wall:.0f} tok/s), "
              f"mesh={describe_mesh(mesh)}")


if __name__ == "__main__":
    main()
