"""Device-resident continuous-batching engine: ragged parity (chunked and
flash prefill), EOS in the fused loop, slot reuse, input validation, and the
one-host-transfer-per-call regression guard."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.catalog import ARCHITECTURES
from repro.models import build_model
from repro.serve import Engine, Request, ServeConfig, generate_per_prompt


def _build(arch="llama3.2-1b", attention_impl=None, **serve_kw):
    cfg = ARCHITECTURES[arch].reduced()
    if attention_impl:
        cfg = dataclasses.replace(cfg, attention_impl=attention_impl)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    kw = dict(max_batch=3, max_len=64)
    kw.update(serve_kw)
    return cfg, model, params, Engine(model, params, ServeConfig(**kw))


RAGGED = [[5, 9, 2, 7], [1, 3, 3], [2, 4, 6, 8, 1, 5, 3]]


def test_ragged_batch_matches_single_prompt_generation():
    """Satellite bug: shorter prompts in a ragged batch used to attend to
    pad tokens.  Now every row decodes exactly what it decodes alone."""
    cfg, model, params, eng = _build()
    batched = eng.generate(RAGGED, 5)
    singles = [eng.generate([p], 5)[0] for p in RAGGED]
    assert batched == singles


def test_ragged_batch_matches_reference_loop():
    """Parity against the unpadded batch-1 reference loop (no engine code in
    the oracle path)."""
    cfg, model, params, eng = _build()
    batched = eng.generate(RAGGED, 5)
    oracle = generate_per_prompt(model, params, RAGGED, 5, max_len=64)
    assert batched == oracle


def test_ragged_parity_ssm_and_hybrid():
    """SSM/hybrid pad-zeroing keeps the recurrent state of short prompts
    identical to their solo run."""
    for arch in ("mamba2-130m", "zamba2-2.7b"):
        cfg, model, params, eng = _build(arch)
        batched = eng.generate(RAGGED, 4)
        singles = [eng.generate([p], 4)[0] for p in RAGGED]
        assert batched == singles, arch


# one representative per model family (dense / moe / vlm / audio / hybrid);
# mamba2 (ssm) is attention-free, so the hybrid carries the SSM-side check
FLASH_FAMILIES = ["llama3.2-1b", "olmoe-1b-7b", "llama-3.2-vision-11b",
                  "whisper-large-v3", "zamba2-2.7b"]


@pytest.mark.parametrize("arch", FLASH_FAMILIES)
def test_flash_prefill_ragged_parity_all_families(arch):
    """Tentpole acceptance: with attention_impl="flash" the engine's ragged
    prefill routes through the tuned flash kernel and still matches the
    unpadded batch-1 oracle token-for-token."""
    cfg, model, params, eng = _build(arch, attention_impl="flash")
    prompts = [[t % cfg.vocab_size for t in p] for p in RAGGED]
    extra = {k: jnp.zeros((len(prompts),) + s.shape[1:], s.dtype)
             for k, s in model.extra_inputs(len(prompts)).items()}
    batched = eng.generate(prompts, 5, extra_inputs=extra or None)
    oracle = generate_per_prompt(model, params, prompts, 5, max_len=64,
                                 extra_inputs=extra or None)
    assert batched == oracle, arch


def test_flash_prefill_non_divisible_prompt_length():
    """A prompt length that is not divisible by the (tuned or default) bq
    exercises the kernel's internal left-padding inside the engine."""
    cfg, model, params, eng = _build(attention_impl="flash", max_len=128)
    prompts = [[(i * 7 + 3) % cfg.vocab_size for i in range(37)],
               [(i * 5 + 1) % cfg.vocab_size for i in range(11)]]
    batched = eng.generate(prompts, 4)
    oracle = generate_per_prompt(model, params, prompts, 4, max_len=128)
    assert batched == oracle


def test_flash_prefill_provenance_in_stats():
    """Engine.stats() must surface which tuned (bq, bk) blocks prefill used
    and which registry tier satisfied the lookup."""
    cfg, model, params, eng = _build(attention_impl="flash")
    eng.generate([[1, 2, 3]], 2)
    st = eng.stats()
    lookups = st["prefill_flash_lookups"]
    assert lookups, "flash prefill lookups were not recorded"
    for shape, info in lookups.items():
        assert info["source"] in ("exact", "nearest", "generic", "default",
                                  "fallback")
        assert "x" in info["tile"]
    # chunked engines don't report flash provenance
    _, _, _, eng_c = _build()
    eng_c.generate([[1, 2, 3]], 2)
    assert eng_c.stats()["prefill_flash_lookups"] == {}


def test_eos_stops_inside_fused_loop():
    cfg, model, params, eng = _build(max_batch=2)
    # second token of the free-running generation, used as EOS below
    free = eng.generate([[3, 1, 4]], 6)[0]
    eos = free[1]
    eng_eos = Engine(model, params, ServeConfig(max_batch=2, max_len=64,
                                                eos_token=eos))
    if free[0] == eos:              # degenerate repeat: stops on first token
        assert eng_eos.generate([[3, 1, 4]], 6)[0] == free[:1]
        return
    out = eng_eos.generate([[3, 1, 4]], 6)[0]
    assert out == free[:2]          # EOS itself is kept, nothing after it
    # EOS applies per slot: pair a stopping row with a free-running one
    outs = eng_eos.generate([[3, 1, 4], [1, 3, 3]], 6)
    assert outs[0] == free[:2]
    assert len(outs[1]) in range(1, 7)


def test_slot_reuse_across_generate_calls():
    cfg, model, params, eng = _build()
    first = eng.generate(RAGGED, 5)
    second = eng.generate(RAGGED, 5)
    assert first == second          # stale slot KV never leaks into a rerun
    st = eng.stats()
    assert st["cache_allocs"] == 1  # one KV pool for the engine's lifetime
    assert st["slot_reuses"] >= 3
    assert st["slots_admitted"] == st["slots_evicted"] == 6


def test_more_prompts_than_slots_run_in_waves():
    cfg, model, params, eng = _build(max_batch=2, scheduler="wave")
    prompts = RAGGED + [[9, 9, 1]]
    outs = eng.generate(prompts, 4)
    waves = eng.stats()["waves"]
    assert waves == 2
    assert eng.stats()["device_transfers"] == waves   # one fetch per wave
    singles = [eng.generate([p], 4)[0] for p in prompts]
    assert outs == singles


def test_exactly_one_host_transfer_per_generate(monkeypatch):
    """Regression guard for the tentpole: the decode loop must not sync the
    host per token — one device_get per generate call (chunked continuous decode has its own
    transfer contract — see test_recompile_count.py)."""
    cfg, model, params, eng = _build(scheduler="wave")
    eng.generate(RAGGED, 6)                      # compile outside the count
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda *a, **k: (
        calls.append(1), real(*a, **k))[1])
    eng.generate(RAGGED, 6)
    assert len(calls) == 1
    calls.clear()
    eng.generate([[1, 2]], 3)
    assert len(calls) == 1


def test_empty_prompt_and_empty_batch_raise():
    cfg, model, params, eng = _build()
    with pytest.raises(ValueError, match="at least one prompt"):
        eng.generate([], 4)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.generate([[1, 2], []], 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.generate([[1, 2]], 0)


def test_overlong_request_raises_without_leaking_slots():
    # wave semantics: the continuous scheduler admits this request (12 + 8
    # fits its token pool); test_continuous_token_capacity covers that path
    cfg, model, params, eng = _build(max_len=16, scheduler="wave")
    with pytest.raises(ValueError, match="exceeds"):
        eng.generate([[1] * 12], 8)
    # the rejected request must not have consumed a slot
    outs = eng.generate([[1, 2]], 3)
    assert len(outs[0]) == 3


def test_mixed_wave_capacity_no_over_rejection():
    """Headline bugfix: the engine used to reject a wave when
    max(prompt) + max(max_new) ACROSS the wave exceeded max_len, even though
    each request fit on its own.  Wave packing must schedule a
    long-prompt/small-budget and a short-prompt/big-budget request into
    separate waves and complete both."""
    cfg, model, params, eng = _build(max_batch=2, max_len=16,
                                      scheduler="wave")
    h_a = eng.submit(Request(prompt=[1] * 12, max_new_tokens=3))
    h_b = eng.submit(Request(prompt=[2, 3], max_new_tokens=12))
    eng.run()                           # used to raise: 12 + 12 > 16
    assert len(h_a.result(timeout=0).tokens) == 3
    assert len(h_b.result(timeout=0).tokens) == 12
    assert eng.stats()["waves"] == 2    # packed apart, not rejected together
    # each request decodes exactly what it decodes alone
    assert h_a.result(timeout=0).tokens == eng.generate([[1] * 12], 3)[0]
    assert h_b.result(timeout=0).tokens == eng.generate([[2, 3]], 12)[0]


def test_wave_packing_keeps_compatible_requests_batched():
    """Packing must not needlessly split: requests that fit jointly still
    share one wave (one prefill + one fused decode)."""
    cfg, model, params, eng = _build(max_batch=3, max_len=64,
                                      scheduler="wave")
    for p in RAGGED:
        eng.submit(Request(prompt=p, max_new_tokens=5))
    results = eng.run()
    assert eng.stats()["waves"] == 1
    assert len(results) == 3


def test_submit_rejects_oversized_request_fast():
    """Per-request validation at enqueue time: an oversized request fails at
    submit() instead of bricking the wave it would have joined."""
    cfg, model, params, eng = _build(max_len=16, scheduler="wave")
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(Request(prompt=[1] * 12, max_new_tokens=8))  # 12+8 > 16
    assert eng.stats()["requests"] == 0
    # the queue is untouched: a valid request still round-trips
    h = eng.submit(Request(prompt=[1, 2], max_new_tokens=3))
    eng.run()
    assert len(h.result(timeout=0).tokens) == 3


def test_near_capacity_bucket_clamped_to_max_len():
    """Satellite bugfix: _bucket_len used to overshoot max_len for
    near-capacity prompts, falling back to exact per-length pad sizes (a
    recompile per distinct prompt length).  The clamped bucket keeps nearby
    long prompts in ONE bucket — and stays token-for-token exact."""
    from repro.serve import generate_per_prompt
    cfg, model, params, eng = _build(max_len=48, scheduler="wave")
    for plen in (38, 40):
        prompt = [(i * 7 + 3) % cfg.vocab_size for i in range(plen)]
        out = eng.generate([prompt], 4)[0]
        assert out == generate_per_prompt(model, params, [prompt], 4,
                                          max_len=48)[0]
    buckets = eng.stats()["prefill_plen_buckets"]
    assert len(buckets) == 1, buckets   # 38 and 40 share one clamped bucket
    assert buckets[0] + 4 <= 48         # and it honours the slot capacity


def test_submit_run_queue_api():
    cfg, model, params, eng = _build(max_batch=2)
    handles = [eng.submit(Request(prompt=p, max_new_tokens=4))
               for p in RAGGED]
    results = eng.run()
    assert sorted(r.request_id for r in results) == \
        sorted(h.request_id for h in handles)
    assert handles[0].result(timeout=0).tokens == \
        eng.generate([RAGGED[0]], 4)[0]


def test_run_with_extras_requires_rows():
    cfg, model, params, eng = _build("whisper-large-v3", max_batch=2)
    extra = {k: jax.numpy.zeros((1,) + sds.shape[1:], sds.dtype)
             for k, sds in model.extra_inputs(1).items()}
    # no row= -> can't index extras
    eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=2))
    with pytest.raises(ValueError, match="row"):
        eng.run(extra_inputs=extra)


def test_engine_stats_surface_tile_provenance():
    cfg, model, params, eng = _build()
    eng.generate([[1, 2, 3]], 2)
    st = eng.stats()
    lookups = st["decode_tile_lookups"]
    assert lookups, "decode GEMM shapes were not traced"
    for shape, info in lookups.items():
        assert info["source"] in ("exact", "nearest", "generic", "default",
                                  "fallback")
        assert "x" in info["tile"]
    assert st["registry_hit_stats"]


def test_first_sample_key_decorrelated_from_loop():
    """Satellite bug: the first token used to be sampled with the parent
    PRNG key that the loop then split again, correlating the first two
    samples.  Pin the fixed key schedule with an oracle: the first token
    must come from a fresh split, not from the wave key itself."""
    cfg, model, params, eng = _build(temperature=1.5, max_batch=1,
                                     scheduler="wave")
    out = eng.generate([[1, 2, 3, 4]], 1)[0]
    # oracle: replicate the engine's padding (bucket 8, pad token 0) and
    # key schedule (seed key -> per-wave split -> pre-sample split)
    batch = {"tokens": jnp.asarray([[0, 0, 0, 0, 1, 2, 3, 4]], jnp.int32),
             "kv_start": jnp.asarray([4], jnp.int32)}
    logits, _ = jax.jit(model.prefill)(params, batch, model.init_cache(1, 64))
    _, wave_key = jax.random.split(jax.random.PRNGKey(0))
    _, sub = jax.random.split(wave_key)
    expected = int(jax.random.categorical(sub, logits / 1.5, axis=-1)[0])
    buggy = int(jax.random.categorical(wave_key, logits / 1.5, axis=-1)[0])
    assert out[0] == expected
    assert expected != buggy        # the regression is distinguishable
    # same seed -> deterministic across engines
    cfg2, model2, params2, eng2 = _build(temperature=1.5, max_batch=1,
                                         scheduler="wave")
    assert eng2.generate([[1, 2, 3, 4]], 1)[0] == out


def test_failed_call_frees_slots_and_queue():
    """A request that dies mid-wave (here: whisper without its required
    encoder_embeds) must neither leak its KV slot nor leave queued requests
    behind for the next call."""
    cfg, model, params, eng = _build("whisper-large-v3", max_batch=1)
    with pytest.raises(KeyError):
        eng.generate([[1, 2, 3]], 2)
    extra = {k: jnp.zeros((1,) + sds.shape[1:], sds.dtype)
             for k, sds in model.extra_inputs(1).items()}
    outs = eng.generate([[1, 2, 3]], 2, extra_inputs=extra)
    assert len(outs[0]) == 2
    st = eng.stats()
    assert st["slots_admitted"] == st["slots_evicted"]


def test_varied_max_new_shares_one_decode_compile():
    """max_new is bucketed before becoming the loop's static width, so
    near-miss budgets don't each pay a full while_loop compile — and the
    bucket must not change the tokens produced."""
    cfg, model, params, eng = _build()
    a = eng.generate(RAGGED, 5)
    b = eng.generate(RAGGED, 6)     # same bucket (8) as 5
    assert [x[:5] for x in b] == a  # shared prefix: bucketing is invisible


def test_decode_unroll_config_and_heuristic_provenance():
    """ServeConfig.decode_unroll is the top of the resolution order; with no
    config and no tuned entry, a single-device engine falls back to the
    u1 heuristic.  Both value and provenance surface in stats()."""
    cfg, model, params, eng = _build(decode_unroll=2)
    out_u2 = eng.generate(RAGGED, 5)
    st = eng.stats()
    assert st["decode_unroll"] == 2
    assert st["decode_unroll_source"] == "config"
    _, _, _, eng_h = _build()
    out_u1 = eng_h.generate(RAGGED, 5)
    st = eng_h.stats()
    assert st["decode_unroll"] == 1
    assert st["decode_unroll_source"] == "heuristic"
    # the unroll changes the loop schedule, never the tokens
    assert out_u2 == out_u1


def test_decode_unroll_tuned_entry_resolves_and_keeps_parity():
    """A decode_loop entry in the registry (shape = (max_batch, max_len))
    must win over the heuristic, report tuned provenance, and decode the
    same tokens as an unrolled=1 engine."""
    from repro.core import (GLOBAL_REGISTRY, OP_DECODE_LOOP, DecodeLoopConfig)
    import jax.numpy as _jnp
    cfg, model, params, _ = _build()
    dt = _jnp.dtype(cfg.dtype).name
    GLOBAL_REGISTRY.put_op(OP_DECODE_LOOP, DecodeLoopConfig(2),
                           "cpu-interpret", cfg.dtype, (3, 64))
    try:
        eng = Engine(model, params,
                     ServeConfig(max_batch=3, max_len=64,
                                 hardware="cpu-interpret"))
        out = eng.generate(RAGGED, 5)
        st = eng.stats()
        assert st["decode_unroll"] == 2
        assert st["decode_unroll_source"] == "tuned:exact"
        ref = Engine(model, params,
                     ServeConfig(max_batch=3, max_len=64, decode_unroll=1,
                                 hardware="cpu-interpret"))
        assert out == ref.generate(RAGGED, 5)
    finally:
        # drop the entry: provenance assertions elsewhere expect a clean
        # registry (nearest-tier would otherwise satisfy nearby shapes)
        GLOBAL_REGISTRY._exact.pop((OP_DECODE_LOOP, "cpu-interpret", dt),
                                   None)


# -- continuous scheduler (paged KV cache) -----------------------------------

@pytest.mark.parametrize("arch", FLASH_FAMILIES)
def test_continuous_matches_wave_engine_all_families(arch):
    """Tentpole acceptance: the paged continuous engine is token-for-token
    identical to the wave engine AND the per-prompt oracle across every
    model family, on ragged prompts with flash prefill."""
    cfg, model, params, eng_c = _build(arch, attention_impl="flash")
    eng_w = Engine(model, params, ServeConfig(max_batch=3, max_len=64,
                                              scheduler="wave"))
    prompts = [[t % cfg.vocab_size for t in p] for p in RAGGED]
    extra = {k: jnp.zeros((len(prompts),) + s.shape[1:], s.dtype)
             for k, s in model.extra_inputs(len(prompts)).items()}
    out_c = eng_c.generate(prompts, 5, extra_inputs=extra or None)
    out_w = eng_w.generate(prompts, 5, extra_inputs=extra or None)
    assert out_c == out_w, arch
    oracle = generate_per_prompt(model, params, prompts, 5, max_len=64,
                                 extra_inputs=extra or None)
    assert out_c == oracle, arch
    assert eng_c.stats()["scheduler"] == "continuous"
    # one pass admitting rows at buckets 8, 64 and 32, then one with a
    # partial prefix hit on the longest (two 16-token pages) beside others.
    # The MoE layer routes left-pad tokens into expert capacity, so its
    # tokens depend on the pad length: there each row must serve what it
    # serves alone (at its own bucket, as admission now prefills it),
    # while the wave engine pads every row to the longest one's bucket.
    # max_len 128 keeps the wave engine's bucket for 40 tokens at 64 (max_len
    # 64 clamps it to 56).
    eng_c = Engine(model, params, ServeConfig(max_batch=3, max_len=128))
    eng_w = Engine(model, params, ServeConfig(max_batch=3, max_len=128,
                                              scheduler="wave"))
    solo = Engine(model, params, ServeConfig(max_batch=3, max_len=128,
                                             prefix_cache=False))
    v = cfg.vocab_size
    long_ = [(7 * i + 3) % v for i in range(40)]
    mixed = [[2, 5, 1], long_, [(5 * i + 1) % v for i in range(20)]]
    partial = [long_[:32] + [1, 2, 3], [9, 4],
               [(3 * i + 2) % v for i in range(27)]]
    for prompts in (mixed, partial):
        extra = {k: jnp.zeros((len(prompts),) + s.shape[1:], s.dtype)
                 for k, s in model.extra_inputs(len(prompts)).items()}
        out = eng_c.generate(prompts, 5, extra_inputs=extra or None)
        if cfg.family == "moe":
            assert out == [solo.generate([p], 5)[0] for p in prompts], arch
        else:
            assert out == eng_w.generate(prompts, 5,
                                         extra_inputs=extra or None), arch
    if not extra:           # requests with extra inputs are never cached
        assert eng_c.stats()["prefix_cache"]["hits_partial"] >= 1, arch


def test_continuous_falls_back_to_wave_for_ssm_and_kv_quant():
    """Models with no self-attention KV (pure SSM) or an int8-quantized
    cache transparently keep the wave path, with the reason in stats()."""
    cfg, model, params, eng = _build("mamba2-130m")
    assert eng.stats()["scheduler"] == "wave"
    assert "KV" in eng.stats()["scheduler_forced"]
    out = eng.generate(RAGGED, 4)
    assert out == [eng.generate([p], 4)[0] for p in RAGGED]


def test_continuous_token_capacity_admits_beyond_max_len():
    """Satellite fix: submit() used to enforce prompt + max_new <= max_len
    even for the paged engine, whose true constraint is the token pool.
    12 + 8 > max_len=16 but fits the 3 * 16 = 48-token pool."""
    cfg, model, params, eng = _build(max_len=16)
    assert eng.stats()["capacity_tokens"] == 48
    out = eng.generate([[1] * 12], 8)[0]
    assert out == generate_per_prompt(model, params, [[1] * 12], 8,
                                      max_len=32)[0]
    # the pool itself still bounds a single request, at submit time
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(Request(prompt=[1] * 12, max_new_tokens=48))
    assert eng.stats()["requests"] == 1      # the rejected one never queued
    with pytest.raises(ValueError, match="exceeds"):
        eng.generate([[1] * 12], 48)


def test_continuous_stats_report_paged_provenance():
    """stats() must surface the paged-cache telemetry: page size + its
    resolution provenance, pool utilization, and the admission/eviction/
    preemption counters."""
    cfg, model, params, eng = _build(page_size=4)
    eng.generate(RAGGED, 5)
    st = eng.stats()
    assert st["scheduler"] == "continuous"
    assert st["scheduler_forced"] is None
    assert st["page_size"] == 4
    assert st["page_size_source"] == "config"
    assert st["admissions"] == st["evictions"] == 3
    assert st["preemptions"] == 0
    pages = st["pages"]
    assert pages["page_size"] == 4
    # drained pool: the only pages still out are the prefix cache's pins
    assert pages["used_pages"] == st["prefix_cache"]["pinned_pages"]
    assert pages["high_water_pages"] > 0
    assert 0.0 <= pages["utilization"] <= 1.0
    eng.clear_prefix_cache()
    pages = eng.stats()["pages"]
    assert pages["used_pages"] == 0          # cache cleared: all pages home
    assert pages["free_pages"] == pages["usable_pages"]
    assert pages["alloc_count"] == pages["free_count"]
    assert st["chunks"] >= 1
    assert st["admission_prefills"] >= 1
    # with no explicit page_size the tuned paged_attn entry resolves it
    _, _, _, eng_t = _build(hardware="cpu-interpret")
    eng_t.generate([[1, 2, 3]], 2)
    src = eng_t.stats()["page_size_source"]
    assert src.startswith("tuned:") or src in ("default", "fallback")


def test_continuous_preemption_restart_is_exact():
    """A pool too small for every row's chunk growth forces youngest-first
    preemption; victims requeue at the front, restart cleanly, and still
    decode their exact solo tokens (greedy determinism)."""
    cfg, model, params, eng = _build(capacity_tokens=40, page_size=8)
    prompts = RAGGED + [[9, 9, 1]]
    handles = [eng.submit(Request(prompt=p, max_new_tokens=10))
               for p in prompts]
    eng.run()
    st = eng.stats()
    assert st["preemptions"] >= 1
    # drained: only the prefix cache's pins are still out
    assert st["pages"]["used_pages"] == st["prefix_cache"]["pinned_pages"]
    eng.clear_prefix_cache()
    assert eng.stats()["pages"]["used_pages"] == 0
    for h, p in zip(handles, prompts):
        assert h.result(timeout=0).tokens == generate_per_prompt(
            model, params, [p], 10, max_len=64)[0]


def test_admission_prefills_each_row_alone_at_its_own_bucket():
    """Single device: one pass admitting prompts of 1500, 40 and 300 tokens
    makes three one-row prefill calls, shortest first, at buckets 64, 512
    and 2048; each call's tokens are right-aligned at its own bucket, its
    KV columns land in the row's pages (pad columns in TRASH) and its slot
    map names the row's slot."""
    from repro.serve.kv_pages import TRASH_PAGE
    cfg, model, params, eng = _build(max_len=2048, page_size=16)
    v = cfg.vocab_size
    prompts = [[(i * 7 + 1) % v for i in range(1500)],
               [(i * 5 + 2) % v for i in range(40)],
               [(i * 3 + 4) % v for i in range(300)]]
    eng._ensure_pool()
    real = eng._build_admit_fn()
    calls = []

    def recording(params, batch, scratch, pools, fixed, cur, key, dest,
                  slot_map):
        slot = int(slot_map[0])
        calls.append(dict(tokens=np.asarray(batch["tokens"]),
                          kv_start=np.asarray(batch["kv_start"]),
                          dest=np.asarray(dest),
                          slot_map=np.asarray(slot_map),
                          pages=list(eng._csched.rows[slot].pages)))
        return real(params, batch, scratch, pools, fixed, cur, key, dest,
                    slot_map)

    eng._admit_fn = recording
    handles = [eng.submit(Request(prompt=p, max_new_tokens=2))
               for p in prompts]
    eng.run()
    assert eng.stats()["admission_prefills"] == 3
    page = 16
    # slots go in arrival order (0, 1, 2), calls by prompt length
    for c, (slot, n, plen) in zip(calls, [(1, 40, 64), (2, 300, 512),
                                          (0, 1500, 2048)]):
        assert c["tokens"].shape == (1, plen)
        assert c["slot_map"].tolist() == [slot]
        assert c["kv_start"].tolist() == [plen - n]
        assert c["tokens"][0, plen - n:].tolist() == prompts[slot]
        assert not c["tokens"][0, :plen - n].any()
        logical = np.arange(n)
        pages = np.asarray(c["pages"])
        assert c["dest"][0, plen - n:].tolist() == (
            pages[logical // page] * page + logical % page).tolist()
        assert (c["dest"][0, :plen - n] // page == TRASH_PAGE).all()
    assert len(calls) == 3
    assert eng.stats()["prefill_plen_buckets"] == [64, 512, 2048]
    # the wave engine, prefilling all three at 2048, serves the same tokens
    wave = Engine(model, params, ServeConfig(max_batch=3, max_len=2048,
                                             scheduler="wave"))
    assert [h.result(timeout=0).tokens for h in handles] == \
        wave.generate(prompts, 2)
