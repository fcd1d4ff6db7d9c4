"""The trace-time all-reduce counter (``core.allreduce_tally``) and the
engine's count of the model steps a decode-chunk run took, by which it
multiplies a step's bytes per run."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.catalog import ARCHITECTURES
from repro.core import (allreduce_repeat, allreduce_tally, execution_context,
                        matmul)
from repro.distributed.ctx import activation_policy
from repro.distributed.sharding import serving_rules
from repro.launch.mesh import build_mesh
from repro.models import build_model
from repro.models.transformer import layer_scan
from repro.models.layers import COL, ROW
from repro.serve import Engine, Request, ServeConfig


def test_row_parallel_psum_counted_once_per_trace():
    """A row-parallel weight's psum adds its per-shard f32 operand when
    the program is traced, scaled by an enclosing loop's trip count; a
    column-parallel one adds nothing; running the compiled program again
    adds nothing.  A one-device mesh keeps the psum (over a 1-wide axis)."""
    mesh = build_mesh("data=1,model=1")
    x = jnp.ones((6, 32), jnp.float32)
    w = jnp.ones((32, 16), jnp.float32) / 32

    def step(x, w):
        with activation_policy(mesh, serving_rules(mesh)):
            with allreduce_repeat(3):
                y = matmul(x, w, w_axes=ROW)
            return matmul(y, w.T, w_axes=COL)

    fn = jax.jit(step)
    with execution_context(backend="pallas-interpret"):
        with allreduce_tally() as outer:
            with allreduce_tally() as inner:
                fn(x, w)
            fn(x, w)                 # cached: nothing traced, nothing added
    assert inner.bytes == outer.bytes == 3 * 6 * 16 * 4


def test_nested_layer_scans_scale_by_every_trip_count():
    """Three units of two layers each (the shape of the hybrid and
    cross-attention stacks) count a row-parallel psum six times a run."""
    mesh = build_mesh("data=1,model=1")
    x = jnp.ones((6, 32), jnp.float32)
    w = jnp.ones((3, 2, 32, 32), jnp.float32) / 32

    def step(x, w):
        with activation_policy(mesh, serving_rules(mesh)):
            def unit(x, wu):
                def layer(x, wl):
                    return matmul(x, wl, w_axes=ROW), None
                return layer_scan(layer, x, wu)[0], None
            return layer_scan(unit, x, w)[0]

    with execution_context(backend="pallas-interpret"):
        with allreduce_tally() as tally:
            jax.jit(step)(x, w)
    assert tally.bytes == 6 * 6 * 32 * 4


class _PerStep(dict):
    """Stands for one byte per decode step, whatever the trace counted, so
    ``allreduce_bytes`` reads the steps the engine multiplies by."""

    def __setitem__(self, key, value):
        super().__setitem__(key, 1)


@pytest.mark.parametrize("news, eos_at", [
    # one row outlives the first chunk and ends on its budget in the third
    ((20,), None),
    # one row ends on its budget at the chunk's last token
    ((8,), None),
    # rows end in different chunks; the last chunk stops early
    ((3, 5, 11), None),
    # the row ends on EOS: its fourth token, budget left
    ((20,), 3),
])
def test_counted_steps_are_the_decode_steps_run(news, eos_at):
    """The engine scales a decode step's bytes by the steps a chunk run
    took, read from the loop's final offset: that count is the number of
    decode steps the program ran, over every chunk of a drain."""
    cfg = ARCHITECTURES["llama3.2-1b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [[5, 9, 2, 11][:2 + i] for i in range(len(news))]
    eos = None
    if eos_at is not None:
        probe = Engine(model, params, ServeConfig(max_batch=3, max_len=64,
                                                  decode_chunk=8))
        h = probe.submit(Request(prompt=prompts[0], max_new_tokens=news[0]))
        probe.run()
        tokens = h.result(timeout=0).tokens
        eos = tokens[eos_at]
        assert eos not in tokens[:eos_at], tokens
    eng = Engine(model, params, ServeConfig(max_batch=3, max_len=64,
                                            decode_chunk=8, eos_token=eos))
    eng._step_allreduce = _PerStep()
    steps = []
    real_sample = Engine._sample

    def sample(logits, key):
        if logits.shape[0] == eng.cfg.max_batch:     # a decode step
            jax.debug.callback(lambda: steps.append(1))
        return real_sample(eng, logits, key)

    eng._sample = sample
    hs = [eng.submit(Request(prompt=p, max_new_tokens=n))
          for p, n in zip(prompts, news)]
    eng.run()
    served = [len(h.result(timeout=0).tokens) for h in hs]
    if eos_at is not None:
        assert served == [eos_at + 1], served
    assert eng.stats()["allreduce_bytes"] == len(steps) > 0
