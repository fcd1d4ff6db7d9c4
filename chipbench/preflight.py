#!/usr/bin/env python3
"""Compile a configuration's serving steps for a described TPU v5e.

  JAX_PLATFORMS=cpu python3 chipbench/preflight.py chatglm3-6b

No chip is needed: the TPU compiler compiles the model's prefill (the
admission program's core) at ``max_batch`` rows of the longest admission
bucket, and one decode step over the widest decode view, for a chip that is
only described, and prints each program's ``memory_analysis()`` beside the
bytes of the weights and of the paged KV pool the configuration file asks
for.  A program that would not fit is refused here, before chip time is
spent; the admission program holds a little more than this prefill (the
scatter into the pool).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("config", help="name of chipbench/configs/<name>.json")
    ap.add_argument("--plen", type=int, default=None,
                    help="admission bucket (default: half of the "
                         "configuration's serve.max_len)")
    ap.add_argument("--width", type=int, default=None,
                    help="decode view width in tokens (default: "
                         "serve.max_len)")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.configs.catalog import get_config
    from repro.core import execution_context
    from repro.models import build_model

    jax.config.update("jax_enable_compilation_cache", False)
    config = json.loads((HERE / "configs" / f"{args.config}.json")
                        .read_text())
    sv, arch = config["serve"], config["arch"]
    prog = config["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]),
                              **prog.get("overrides", {}))
    model = build_model(cfg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)

    b = sv["max_batch"]
    plen = args.plen or sv["max_len"] // 2
    width = args.width or sv["max_len"]
    params = shapes(model.abstract())
    weight_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(params))
    kv_token = (2 * arch["layers"] * arch["kv_heads"] * arch["head_dim"]
                * jnp.dtype(arch["dtype"]).itemsize)
    print(f"[weights] {weight_bytes} bytes; KV {kv_token} bytes/token, pool "
          f"{sv['capacity_tokens']} tokens = "
          f"{sv['capacity_tokens'] * kv_token} bytes")
    i32 = lambda s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    with execution_context(backend="pallas-tpu", hardware="tpu-v5e"):
        cache = shapes(jax.eval_shape(lambda: model.init_cache(b, plen)))
        pre = jax.jit(model.prefill).lower(
            params, {"tokens": i32((b, plen)), "kv_start": i32((b,))},
            cache).compile()
        print(f"[prefill {b}x{plen}] {pre.memory_analysis()}")
        cache = shapes(jax.eval_shape(lambda: model.init_cache(b, width)))
        dec = jax.jit(model.decode_step).lower(
            params, i32((b, 1)), cache, i32(()), i32((b,))).compile()
        print(f"[decode {b} rows x {width}] {dec.memory_analysis()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
