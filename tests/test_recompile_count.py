"""Recompile-count regression tests — the dynamic complement of the static
trace-purity lint (``scripts/analyze.py lint``).

The static checks prove nothing syncs *inside* a trace; these prove the
engine's bucketing policy keeps the number of traces themselves bounded.
Every distinct (plen bucket, width bucket) pair costs one XLA compile; if
bucketing regressed to per-exact-length shapes, steady-state serving would
recompile per request — the exact pathology PR 2 removed.  jit's
compilation-cache counter (``jitted._cache_size()``) is the ground truth:
it counts compiled variants, not calls.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.serve.engine import _bucket_len


@pytest.fixture(scope="module")
def engine_setup():
    from repro.configs.catalog import ARCHITECTURES
    from repro.models import build_model
    from repro.serve import Engine, ServeConfig
    cfg = ARCHITECTURES["llama3.2-1b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, ServeConfig(max_batch=4, max_len=64,
                                            scheduler="wave"))
    return cfg, eng


@pytest.fixture(scope="module")
def continuous_setup():
    from repro.configs.catalog import ARCHITECTURES
    from repro.models import build_model
    from repro.serve import Engine, ServeConfig
    cfg = ARCHITECTURES["llama3.2-1b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, ServeConfig(max_batch=4, max_len=64))
    assert eng.stats()["scheduler"] == "continuous"
    return cfg, eng


def _gen(eng, cfg, lengths, new_tokens):
    prompts = [[(3 * i + j) % cfg.vocab_size for j in range(n)]
               for i, n in enumerate(lengths)]
    return eng.generate(prompts, new_tokens)


def test_prefill_compiles_bounded_by_plen_buckets(engine_setup):
    cfg, eng = engine_setup
    # lengths spanning two plen buckets (<=8 -> 8, 9..16 -> 16), one width
    _gen(eng, cfg, [3, 5], 4)
    _gen(eng, cfg, [12, 14], 4)
    _gen(eng, cfg, [4, 15], 4)
    buckets = eng.stats()["prefill_plen_buckets"]
    assert buckets == [8, 16]
    assert eng._prefill._cache_size() <= len(buckets), (
        f"{eng._prefill._cache_size()} prefill compiles for "
        f"{len(buckets)} plen buckets — bucketing is leaking shapes")


def test_decode_loop_compiles_bounded_by_width_buckets(engine_setup):
    cfg, eng = engine_setup
    # max_new_tokens 4 and 7 share the width-8 bucket; 12 opens width 16
    _gen(eng, cfg, [3], 4)
    _gen(eng, cfg, [3], 7)
    _gen(eng, cfg, [3], 12)
    widths = {_bucket_len(4), _bucket_len(7), _bucket_len(12)}
    assert widths == {8, 16}
    assert eng._loop is not None
    assert eng._loop._cache_size() <= len(widths), (
        f"{eng._loop._cache_size()} loop compiles for width buckets "
        f"{sorted(widths)} — (width, unroll) signature is leaking")


def test_steady_state_adds_no_compiles(engine_setup):
    """Repeating previously-seen shapes must hit the jit cache exactly."""
    cfg, eng = engine_setup
    out1 = _gen(eng, cfg, [3, 12], 4)
    before = (eng._prefill._cache_size(), eng._loop._cache_size())
    out2 = _gen(eng, cfg, [3, 12], 4)
    after = (eng._prefill._cache_size(), eng._loop._cache_size())
    assert after == before, (
        f"steady-state generate recompiled: {before} -> {after}")
    assert out1 == out2


def test_cache_counter_is_live():
    """Guard the guard: _cache_size must actually count compilations, or
    the bounds above would vacuously pass on a broken counter."""
    calls = jax.jit(lambda x: x + 1)
    assert calls._cache_size() == 0
    calls(jnp.zeros((2,)))
    assert calls._cache_size() == 1
    calls(jnp.zeros((2,)))           # cache hit
    assert calls._cache_size() == 1
    calls(jnp.zeros((3,)))           # new shape -> new compile
    assert calls._cache_size() == 2


# -- continuous scheduler (paged KV) -----------------------------------------

def test_continuous_steady_state_zero_recompiles(continuous_setup):
    """Admission/eviction churn in steady state must be compile-free: the
    chunk fn is keyed only on (width bucket, chunk, unroll) and the admit fn
    on the plen bucket, so repeating a workload whose shapes were all seen
    before must add ZERO compiled variants to either."""
    cfg, eng = continuous_setup
    # 6 requests over 4 slots with budgets spanning 2 chunks: mid-decode
    # evictions, a second admission wave, several width buckets
    lengths = [3, 5, 12, 4, 7, 9]
    out1 = _gen(eng, cfg, lengths, 12)
    assert eng.stats()["admissions"] >= 6          # churn actually happened
    assert eng.stats()["chunks"] >= 2
    before = (eng._chunk_fn._cache_size(), eng._admit_fn._cache_size())
    out2 = _gen(eng, cfg, lengths, 12)
    after = (eng._chunk_fn._cache_size(), eng._admit_fn._cache_size())
    assert after == before, (
        f"steady-state continuous decode recompiled: {before} -> {after}")
    assert out1 == out2


def test_continuous_admission_compiles_once_per_plen_bucket(
        continuous_setup):
    """Admission prefills each row alone at its own bucket, and the call's
    row count is fixed per engine: one compile per plen bucket, however
    rows of different lengths share a pass."""
    cfg, eng = continuous_setup
    _gen(eng, cfg, [3, 12, 30, 5], 4)       # buckets 8, 16, 32 in one pass
    _gen(eng, cfg, [30], 4)
    _gen(eng, cfg, [12, 3], 4)
    buckets = eng.stats()["prefill_plen_buckets"]
    assert {8, 16, 32} <= set(buckets)
    assert eng._admit_fn._cache_size() == len(buckets), (
        f"{eng._admit_fn._cache_size()} admission compiles for plen "
        f"buckets {buckets}")


def test_continuous_one_device_get_per_chunk(continuous_setup, monkeypatch):
    """The continuous drain's host-transfer contract: exactly one
    device_get per decode chunk — admission, eviction and block-table
    bookkeeping are host-side and must not add transfers."""
    cfg, eng = continuous_setup
    _gen(eng, cfg, [3, 5, 12, 4], 12)            # compile outside the count
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda *a, **k: (
        calls.append(1), real(*a, **k))[1])
    chunks0 = eng.stats()["chunks"]
    _gen(eng, cfg, [3, 5, 12, 4, 7, 9], 12)
    chunks = eng.stats()["chunks"] - chunks0
    assert chunks >= 2
    assert len(calls) == chunks, (
        f"{len(calls)} host transfers for {chunks} chunks")
