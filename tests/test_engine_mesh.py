"""Mesh-sharded serving: spec parsing, topology plumbing, and the tentpole
token-for-token parity guarantee (1-device engine == data=4,model=2 mesh).

The parity tests need 8 devices; in-process versions run when the session
already exposes them (the CI multi-device leg sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) and a slow
subprocess version forces them for single-device sessions (full tier).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.launch.mesh import MESH_AXES, build_mesh, describe_mesh, parse_mesh_spec

# one representative per model family (dense / ssm / moe / vlm / audio)
FAMILIES = ["llama3.2-1b", "mamba2-130m", "olmoe-1b-7b",
            "llama-3.2-vision-11b", "whisper-large-v3"]

PROMPTS = [[5, 9, 2, 7], [1, 3, 3], [2, 4, 6, 8, 1, 5, 3], [9, 9, 1],
           [4, 4], [7, 1, 2, 3, 4], [8, 8, 8], [1, 2]]


# ---------------------------------------------------------------------------
# Spec parsing / mesh construction (no multi-device requirement)
# ---------------------------------------------------------------------------

def test_parse_mesh_spec():
    assert parse_mesh_spec("data=4,model=2") == {"data": 4, "model": 2}
    assert list(parse_mesh_spec("model=2,data=4")) == ["model", "data"]
    assert parse_mesh_spec("pod=2, data=2 , model=1") == {
        "pod": 2, "data": 2, "model": 1}


@pytest.mark.parametrize("bad", [
    "", "data", "data=4,data=2", "ring=4", "data=x", "data=0", "data=-1",
])
def test_parse_mesh_spec_rejects(bad):
    with pytest.raises(ValueError, match="mesh spec"):
        parse_mesh_spec(bad)


def test_build_mesh_none_and_trivial():
    assert build_mesh(None) is None
    assert build_mesh("") is None
    mesh = build_mesh("data=1,model=1")
    assert mesh.axis_names == ("data", "model")
    assert describe_mesh(mesh) == {"devices": 1,
                                   "axes": {"data": 1, "model": 1},
                                   "label": "data1xmodel1"}
    assert describe_mesh(None) == {"devices": 1, "axes": None, "label": None}


def test_build_mesh_auto_uses_all_devices():
    mesh = build_mesh("auto")
    assert mesh.axis_names == ("data",)
    assert mesh.size == len(jax.devices())


def test_build_mesh_too_many_devices_is_actionable():
    n = len(jax.devices()) * 2
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        build_mesh(f"data={n}")


def test_mesh_axes_vocabulary_matches_rules():
    """The spec axes the parser admits are exactly the names the sharding
    rules know how to map."""
    from repro.distributed.sharding import ShardingRules
    rules = ShardingRules()
    known = {rules.tensor_axis, rules.fsdp_axis, *rules.batch_axes, "pod"}
    assert set(MESH_AXES) <= known


# ---------------------------------------------------------------------------
# In-process mesh engine tests (run under the CI multi-device leg)
# ---------------------------------------------------------------------------

needs_8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _build(arch, mesh=None):
    from repro.configs.catalog import ARCHITECTURES
    from repro.models import build_model
    from repro.serve import Engine, ServeConfig
    cfg = ARCHITECTURES[arch].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    eng = Engine(model, params,
                 ServeConfig(max_batch=8, max_len=64, mesh=mesh))
    prompts = [[t % cfg.vocab_size for t in p] for p in PROMPTS]
    extra = {k: jnp.zeros((len(prompts),) + s.shape[1:], s.dtype)
             for k, s in model.extra_inputs(len(prompts)).items()}
    return model, params, eng, prompts, (extra or None)


@needs_8
@pytest.mark.parametrize("arch", FAMILIES)
def test_mesh_parity_all_families(arch):
    """Tentpole acceptance: a data=4,model=2 mesh serves token-for-token
    what the single-device engine serves — sharding is a pure layout knob."""
    _, _, base, prompts, extra = _build(arch)
    _, _, meshed, _, _ = _build(arch, mesh="data=4,model=2")
    out_base = base.generate(prompts, 5, extra_inputs=extra)
    out_mesh = meshed.generate(prompts, 5, extra_inputs=extra)
    assert out_mesh == out_base, arch


@needs_8
def test_mesh_continuous_vs_wave_parity():
    """Paged continuous decode on a data=4,model=2 mesh serves the same
    tokens as the wave engine on the SAME mesh, with the page size resolved
    from a mesh-keyed tuned ``paged_attn`` entry."""
    from repro.configs.catalog import ARCHITECTURES
    from repro.models import build_model
    from repro.serve import Engine, ServeConfig
    cfg = ARCHITECTURES["llama3.2-1b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    prompts = [[t % cfg.vocab_size for t in p] for p in PROMPTS]
    cont = Engine(model, params,
                  ServeConfig(max_batch=8, max_len=64, mesh="data=4,model=2"))
    wave = Engine(model, params,
                  ServeConfig(max_batch=8, max_len=64, mesh="data=4,model=2",
                              scheduler="wave"))
    assert cont.generate(prompts, 5) == wave.generate(prompts, 5)
    # rows of very different lengths in one pass (two calls of 4 rows, at
    # buckets 32 and 64), then a partial prefix hit beside short rows
    v = cfg.vocab_size
    long_ = [(7 * i + 3) % v for i in range(40)]
    mixed = [[2, 5, 1], long_, [(5 * i + 1) % v for i in range(20)],
             [4, 4, 1, 2, 9], [(3 * i) % v for i in range(33)], [6] * 12]
    partial = [long_[:32] + [1, 2, 3], [9, 4],
               [(3 * i + 2) % v for i in range(27)]]
    calls0 = cont.stats()["admission_prefills"]
    assert cont.generate(mixed, 5) == wave.generate(mixed, 5)
    assert cont.stats()["admission_prefills"] - calls0 == 2
    assert cont.generate(partial, 5) == wave.generate(partial, 5)
    assert cont.stats()["prefix_cache"]["hits_partial"] >= 1
    st = cont.stats()
    assert st["scheduler"] == "continuous"
    assert st["chunks"] >= 1 and st["admissions"] >= len(prompts)
    # tuned/cpu-interpret.json carries a data4xmodel2-tagged paged_attn
    # entry; the mesh label is part of the lookup key
    assert st["page_size_source"].startswith("tuned:")
    assert st["page_size"] == 16


@needs_8
def test_mesh_stats_provenance():
    _, _, eng, prompts, _ = _build("llama3.2-1b", mesh="data=4,model=2")
    eng.generate(prompts[:2], 3)
    st = eng.stats()
    assert st["mesh"] == {"devices": 8, "axes": {"data": 4, "model": 2},
                          "label": "data4xmodel2"}
    assert st["sharding"]["rules"]["tensor_axis"] == "model"
    # serving replicates weights over the data axes (inference TP) — the
    # profiling layer showed FSDP-style gathers serializing the decode loop
    assert st["sharding"]["rules"]["fsdp_axis"] is None
    assert sum(st["sharding"]["params"].values()) > 0
    # some param leaves actually landed on the model axis
    assert any("'model'" in k for k in st["sharding"]["params"])


@needs_8
def test_mesh_local_shape_tile_lookups():
    """Tuned-tile lookups on a mesh are keyed by the per-shard LOCAL GEMM
    shape — TP/FSDP change which tuned entry is hit."""
    _, _, eng, prompts, _ = _build("llama3.2-1b", mesh="data=4,model=2")
    eng.generate(prompts[:8], 3)
    lookups = eng.stats()["decode_tile_lookups"]
    assert lookups
    shrunk = 0
    for key, info in lookups.items():
        global_shape = key.split("->")[0]
        m, k, n = (int(x) for x in global_shape.split("x"))
        lm, lk, ln = (int(x) for x in info["local_shape"].split("x"))
        assert lm <= m and lk <= k and ln <= n
        shrunk += (lm, lk, ln) != (m, k, n)
    assert shrunk > 0, f"no lookup used a local shape: {lookups}"
    # square attention projections (wq: embed->ff vs wo: ff->embed) shard
    # the same global (K, N) both ways — both variants must be reported
    variant_keys = [key for key in lookups if "->" in key]
    assert len(variant_keys) >= 2, lookups
    # single-device engines don't report local shapes
    _, _, base, _, _ = _build("llama3.2-1b")
    base.generate(prompts[:2], 3)
    assert all("local_shape" not in v
               for v in base.stats()["decode_tile_lookups"].values())


@needs_8
def test_ambient_use_mesh_is_picked_up():
    """distributed.ctx.use_mesh installs the topology for engines (and
    Model.init) that are not handed a mesh explicitly."""
    from repro.distributed import use_mesh
    mesh = build_mesh("data=4,model=2")
    with use_mesh(mesh):
        _, _, eng, prompts, _ = _build("llama3.2-1b")
        assert eng.mesh is mesh
        out = eng.generate(prompts[:4], 3)
    _, _, base, _, _ = _build("llama3.2-1b")
    assert base.mesh is None
    assert base.generate(prompts[:4], 3) == out


@needs_8
def test_use_mesh_none_clears_ambient_topology():
    """use_mesh(None) inside an outer mesh scope restores single-device
    behavior — the way a parity check builds its unsharded reference."""
    from repro.distributed import current_mesh, use_mesh
    mesh = build_mesh("data=4,model=2")
    with use_mesh(mesh):
        assert current_mesh() is mesh
        with use_mesh(None):
            assert current_mesh() is None
            _, _, eng, _, _ = _build("llama3.2-1b")
            assert eng.mesh is None
        assert current_mesh() is mesh


@needs_8
def test_sharded_init_matches_unsharded_values():
    """Model.init(mesh=...) changes the layout, never the values."""
    from repro.configs.catalog import ARCHITECTURES
    from repro.distributed import sharding as sh
    from repro.models import build_model
    cfg = ARCHITECTURES["llama3.2-1b"].reduced()
    model = build_model(cfg)
    mesh = build_mesh("data=4,model=2")
    plain = model.init(jax.random.PRNGKey(7))
    sharded = model.init(jax.random.PRNGKey(7), mesh=mesh)
    jax.tree_util.tree_map(
        lambda a, b: None if (a == b).all() else pytest.fail("values drifted"),
        plain, sharded)
    # and at least one leaf is genuinely partitioned across devices
    leaves = jax.tree_util.tree_leaves(sharded)
    assert any(not l.sharding.is_fully_replicated for l in leaves)


@needs_8
def test_mesh_streaming_and_warm_cache_parity():
    """The serving front-end composes with sharding: streamed tokens on a
    data=4,model=2 mesh equal the single-device batch output, and a second
    (warm, full-hit) pass through the prefix cache serves the exact same
    tokens — layout and caching are both invisible in the output."""
    from repro.serve import Request, Server
    _, _, base, prompts, _ = _build("llama3.2-1b")
    _, _, meshed, _, _ = _build("llama3.2-1b", mesh="data=4,model=2")
    expected = base.generate(prompts, 5)
    for wanted_hits in (0, len(prompts)):      # cold pass, then warm pass
        before = meshed.stats()["prefix_cache"]["hits_full"]
        events = [[] for _ in prompts]
        with Server(meshed) as srv:
            handles = [srv.submit(Request(prompt=p, max_new_tokens=5,
                                          stream=events[i].append))
                       for i, p in enumerate(prompts)]
            results = [h.result(timeout=600) for h in handles]
        assert [r.tokens for r in results] == expected
        assert [[e.token for e in ev if not e.finished]
                for ev in events] == expected
        hits = meshed.stats()["prefix_cache"]["hits_full"] - before
        assert hits >= wanted_hits


@needs_8
def test_per_token_sync_baseline_mesh_parity():
    """The serving benchmark's sync baseline accepts a mesh so the headline
    ratio compares execution models at fixed placement — sharding it must
    stay pure layout: same tokens on and off the mesh."""
    from repro.configs.catalog import ARCHITECTURES
    from repro.models import build_model
    from repro.serve import PerTokenSyncEngine
    cfg = ARCHITECTURES["llama3.2-1b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    # uniform lengths: the sync baseline has no ragged handling
    prompts = [[(5 * i + j) % cfg.vocab_size for j in range(8)]
               for i in range(8)]
    plain = PerTokenSyncEngine(model, params, max_len=64)
    meshed = PerTokenSyncEngine(model, params, max_len=64,
                                mesh="data=4,model=2")
    assert meshed.mesh is not None and meshed.rules is not None
    out_plain = plain.generate(prompts, 5)
    out_mesh = meshed.generate(prompts, 5)
    assert out_mesh == out_plain
    # the mesh engine's params really are sharded, not just re-placed
    leaves = jax.tree_util.tree_leaves(meshed.params)
    assert any(not l.sharding.is_fully_replicated for l in leaves)


# ---------------------------------------------------------------------------
# Subprocess variant for single-device sessions (full tier)
# ---------------------------------------------------------------------------

_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro.configs.catalog import ARCHITECTURES
    from repro.models import build_model
    from repro.serve import Engine, ServeConfig

    PROMPTS = {prompts!r}
    cfg = ARCHITECTURES[{arch!r}].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    prompts = [[t % cfg.vocab_size for t in p] for p in PROMPTS]
    extra = {{k: jnp.zeros((len(prompts),) + s.shape[1:], s.dtype)
              for k, s in model.extra_inputs(len(prompts)).items()}} or None
    base = Engine(model, params, ServeConfig(max_batch=8, max_len=64))
    out1 = base.generate(prompts, 5, extra_inputs=extra)
    meshed = Engine(model, params,
                    ServeConfig(max_batch=8, max_len=64, mesh="data=4,model=2"))
    out2 = meshed.generate(prompts, 5, extra_inputs=extra)
    st = meshed.stats()
    print("RESULT " + json.dumps({{
        "parity": out1 == out2,
        "devices": st["mesh"]["devices"],
        "axes": st["mesh"]["axes"]}}))
""")


@pytest.mark.slow
@pytest.mark.parametrize("arch", FAMILIES)
def test_mesh_parity_subprocess(arch):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SUBPROC.format(arch=arch, prompts=PROMPTS)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    rec = json.loads(line[len("RESULT "):])
    assert rec["parity"], arch
    assert rec["devices"] == 8
    assert rec["axes"] == {"data": 4, "model": 2}


_PER_SHARD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses, json
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.catalog import ARCHITECTURES
    from repro.core import execution_context, flash_attention, matmul
    from repro.distributed.ctx import activation_policy
    from repro.distributed.sharding import serving_rules
    from repro.launch.mesh import build_mesh
    from repro.models import build_model
    from repro.models.layers import COL, ROW
    from repro.serve import Engine, ServeConfig

    mesh = build_mesh("data=2,model=2")
    rules = serving_rules(mesh)
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    x = jax.random.normal(ks[0], (4, 6, 64))
    w_col = jax.random.normal(ks[1], (64, 96)) / 8
    w_row = jax.random.normal(ks[2], (96, 64)) / 8
    bias = jax.random.normal(ks[3], (64,))
    q = jax.random.normal(ks[4], (4, 24, 4, 16))
    kv = jax.random.normal(ks[5], (4, 24, 2, 16))
    kv_start = jnp.asarray([0, 3, 7, 1], jnp.int32)

    def model_bits(x, w_col, w_row, bias, q, kv, kv_start):
        h = matmul(x, w_col, activation="silu", w_axes=COL)
        y = matmul(h, w_row, bias=bias, activation="gelu", w_axes=ROW)
        a = flash_attention(q, kv, kv, causal=True, kv_start=kv_start,
                            bq=8, bk=8)
        return y, a

    args = (x, w_col, w_row, bias, q, kv, kv_start)
    with execution_context(backend="xla"):
        want = jax.jit(model_bits)(*args)

    def sharded(*a):
        with activation_policy(mesh, rules):
            return model_bits(*a)

    with execution_context(backend="pallas-interpret",
                           hardware="cpu-interpret"):
        fn = jax.jit(sharded)
        got = fn(*args)
        hlo = fn.lower(*args).as_text()
    err = max(float(jnp.abs(g - w).max()) for g, w in zip(got, want))

    cfg = dataclasses.replace(ARCHITECTURES["llama3.2-1b"].reduced(),
                              attention_impl="flash")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    prompts = [[5, 9, 2], [7, 1, 4, 4, 2, 8, 3], [1]]
    with execution_context(backend="pallas-interpret"):
        one = Engine(model, params, ServeConfig(max_batch=4, max_len=32))
        many = Engine(model, params, ServeConfig(max_batch=4, max_len=32,
                                                 mesh="data=1,model=4"))
        out1, out4 = one.generate(prompts, 4), many.generate(prompts, 4)
    print("RESULT " + json.dumps({
        "err": err, "shard_map": hlo.count("sdy.manual_computation"),
        "parity": out1 == out4,
        "local": many.stats()["decode_tile_lookups"]}))
""")


_ADMIT_ROWS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, numpy as np
    from repro.configs.catalog import ARCHITECTURES
    from repro.models import build_model
    from repro.serve import Engine, Request, ServeConfig
    from repro.serve.kv_pages import TRASH_PAGE

    cfg = ARCHITECTURES["llama3.2-1b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    kw = dict(max_batch=4, max_len=64, page_size=16)
    one = Engine(model, params, ServeConfig(**kw))
    four = Engine(model, params, ServeConfig(mesh="data=4,model=1", **kw))
    real = four._build_admit_fn()
    calls = []

    def recording(params, batch, scratch, pools, fixed, cur, key, dest,
                  slot_map):
        calls.append({"shape": list(batch["tokens"].shape),
                      "kv_start": np.asarray(batch["kv_start"]).tolist(),
                      "slot_map": np.asarray(slot_map).tolist(),
                      "dest_pages": np.unique(
                          np.asarray(dest)[3] // 16).tolist()})
        return real(params, batch, scratch, pools, fixed, cur, key, dest,
                    slot_map)

    four._admit_fn = recording
    # a long-lived row and three that finish in the first chunk fill the
    # four slots; the next pass admits three rows beside the live one
    reqs = [([(5 * i + 1) % cfg.vocab_size for i in range(20)], 24),
            ([3, 1, 4], 2), ([1, 5, 9, 2], 2), ([6, 5], 2),
            ([8, 9, 7], 3), ([(3 * i) % cfg.vocab_size for i in range(30)], 3),
            ([2] * 9, 3)]
    out = {}
    for name, eng in (("one", one), ("four", four)):
        hs = [eng.submit(Request(prompt=p, max_new_tokens=n)) for p, n in reqs]
        eng.run()
        out[name] = [h.result(timeout=0).tokens for h in hs]
    print("RESULT " + json.dumps({
        "calls": calls, "parity": out["one"] == out["four"],
        "prefills": [one.stats()["admission_prefills"],
                     four.stats()["admission_prefills"]],
        "trash": TRASH_PAGE}))
""")


def test_admission_calls_take_the_data_axis_rows():
    """On a data=4 mesh of four CPU devices a prefill call carries four
    rows: a pass admitting three makes one call with one pad row, whose
    prompt KV goes only to TRASH and whose out-of-range slot leaves the
    live row untouched (every row serves the single-device tokens)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _ADMIT_ROWS],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    rec = json.loads(line[len("RESULT "):])
    first, second = rec["calls"]
    # rows sorted by prompt length: 2, 3, 4 and 20 tokens
    assert first["shape"] == [4, 32] and first["slot_map"] == [3, 1, 2, 0]
    # rows sorted by prompt length (3, 9, 30 tokens), then the pad row
    assert second["shape"] == [4, 32]
    assert second["slot_map"] == [1, 3, 2, 4]          # 4 = max_batch: pad
    assert second["kv_start"] == [29, 23, 2, 32]
    assert second["dest_pages"] == [rec["trash"]]
    assert rec["prefills"] == [7, 2]                   # one row per call alone
    assert rec["parity"], rec


def test_pallas_kernels_run_per_shard_on_a_mesh():
    """On a mesh the Pallas GEMM (column and row parallel, fused epilogues)
    and flash attention run under shard_map on per-shard operands, agree
    with the XLA path, and a TP engine serves the single-device tokens."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _PER_SHARD],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    rec = json.loads(line[len("RESULT "):])
    assert rec["err"] < 1e-4, rec
    assert rec["shard_map"] >= 3, rec        # two GEMMs + one flash call
    assert rec["parity"], rec
    assert all("local_shape" in v for v in rec["local"].values()), rec


@pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
def test_matmul_on_a_mesh_requires_w_axes(backend):
    """Under a mesh policy every matmul names its weight's logical axes:
    without them a sharded weight would be gathered whole into every
    shard.  The check holds on every backend, so the CPU mesh tests cover
    the call sites the per-shard TPU kernel runs."""
    from repro.core import capture_gemm_shapes, execution_context, matmul
    from repro.distributed.ctx import activation_policy
    from repro.distributed.sharding import serving_rules
    from repro.models.layers import COL
    mesh = build_mesh("data=1,model=1")
    x, w = jnp.ones((2, 3, 16)), jnp.ones((16, 8))
    with execution_context(backend=backend), \
            activation_policy(mesh, serving_rules(mesh)):
        with pytest.raises(ValueError, match="w_axes"):
            matmul(x, w)
        with capture_gemm_shapes(per_shard=True) as calls:
            y = matmul(x, w, w_axes=COL)
    assert y.shape == (2, 3, 8)
    assert calls == [((6, 16, 8), (6, 16, 8))]
    assert matmul(x, w).shape == (2, 3, 8)     # no mesh: no axes needed


_YI_TP4 = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses, json
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.catalog import get_config
    from repro.core import execution_context
    from repro.models import build_model
    from repro.serve import Engine, Request, ServeConfig

    HI = jax.lax.Precision.HIGHEST
    # yi-9b's block at toy widths: 8 query heads over 4 KV heads (one per
    # shard on model=4), no QKV bias, rotary over the whole head, untied
    # head; FFN 172 = 4 x 43 splits into unaligned shards, as 11008 does
    cfg = dataclasses.replace(get_config("yi-9b").reduced(), num_heads=8,
                              num_kv_heads=4, d_ff=172,
                              attention_impl="flash")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim

    def rms(x, scale):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True)
                                 + cfg.norm_eps) * scale

    def rope(x):
        half = hd // 2
        freqs = cfg.rope_theta ** (-np.arange(half, dtype=np.float32) / half)
        ang = np.arange(x.shape[0], dtype=np.float32)[:, None] * freqs
        cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def reference(tokens):
        # plain float32 forward of the whole sequence: logits (S, V)
        p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                   jax.device_get(params))
        s = len(tokens)
        x = p["embedding"][jnp.asarray(tokens)]
        causal = np.tril(np.ones((s, s), bool))
        for layer in range(cfg.num_layers):
            b = jax.tree_util.tree_map(lambda a: a[layer], p["blocks"])
            a = b["attn"]
            y = rms(x, b["ln1"]["scale"])
            q = rope(jnp.dot(y, a["wq"], precision=HI).reshape(s, h, hd))
            k = rope(jnp.dot(y, a["wk"], precision=HI).reshape(s, kvh, hd))
            v = jnp.dot(y, a["wv"], precision=HI).reshape(s, kvh, hd)
            k, v = (jnp.repeat(t, h // kvh, axis=1) for t in (k, v))
            sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(hd)
            pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
            o = jnp.einsum("hqk,khd->qhd", pr, v, precision=HI)
            x = x + jnp.dot(o.reshape(s, h * hd), a["wo"], precision=HI)
            y = rms(x, b["ln2"]["scale"])
            m = b["mlp"]
            f = (jax.nn.silu(jnp.dot(y, m["w_gate"], precision=HI))
                 * jnp.dot(y, m["w_up"], precision=HI))
            x = x + jnp.dot(f, m["w_down"], precision=HI)
        return jnp.dot(rms(x, p["ln_f"]["scale"]), p["lm_head"], precision=HI)

    # decode-step logits leave each chunk run through a host callback (a
    # decode step samples max_batch rows, an admission one)
    decode_logits = []
    real_sample = Engine._sample

    def sample(self, logits, key):
        if logits.shape[0] == self.cfg.max_batch:
            jax.debug.callback(
                lambda v: decode_logits.append(np.asarray(v)), logits)
        return real_sample(self, logits, key)

    Engine._sample = sample
    rng = np.random.default_rng(5)
    # three rows in slots 0-2, admitted at buckets 8, 16 and 32, which
    # finish in different chunks; the last chunk stops early
    reqs = [(rng.integers(0, cfg.vocab_size, n).tolist(), new)
            for n, new in ((5, 6), (13, 9), (30, 5))]
    kw = dict(max_batch=4, max_len=64, page_size=16, decode_chunk=4)
    out = {}
    with execution_context(backend="pallas-interpret"):
        for name, mesh in (("tp4", "data=1,model=4"), ("one", None)):
            eng = Engine(model, params, ServeConfig(mesh=mesh, **kw))
            real_admit = eng._build_admit_fn()
            prefills = []

            def admit(*args, real_admit=real_admit, prefills=prefills):
                res = real_admit(*args)
                prefills.append((int(args[8][0]), args[1]["tokens"].shape[1],
                                 np.asarray(res[4][0])))
                return res

            eng._admit_fn = admit
            decode_logits.clear()
            hs = [eng.submit(Request(prompt=p, max_new_tokens=n))
                  for p, n in reqs]
            eng.run()
            out[name] = dict(
                tokens=[h.result(timeout=0).tokens for h in hs],
                prefills=list(prefills), decode=list(decode_logits),
                allreduce=eng.stats()["allreduce_bytes"])
            # a new drain whose first admission reuses a bucket that the
            # first drain compiled after a chunk had run
            eng.submit(Request(prompt=rng.integers(0, cfg.vocab_size,
                                                   12).tolist(),
                               max_new_tokens=2))
            eng.run()
            out[name]["admit_compiles"] = real_admit._cache_size()

    tp4 = out["tp4"]
    errs, scale = [], 0.0
    for slot, (prompt, _) in enumerate(reqs):
        ref = np.asarray(reference(prompt + tp4["tokens"][slot]))
        scale = max(scale, float(np.abs(ref).max()))
        n = len(prompt)
        (got,) = [lg for s, _, lg in tp4["prefills"] if s == slot]
        errs.append(float(np.abs(got - ref[n - 1]).max()))
        # the k-th decode step reads the row's token k and gives the
        # logits of position n + k, for the row's served tokens after it
        for k in range(len(tp4["tokens"][slot]) - 1):
            errs.append(float(np.abs(tp4["decode"][k][slot] - ref[n + k])
                              .max()))
    d, layers, b = cfg.d_model, cfg.num_layers, kw["max_batch"]
    analytic = (2 * layers * d * 4
                * (sum(plen for _, plen, _ in tp4["prefills"])
                   + b * len(tp4["decode"])))
    print("RESULT " + json.dumps({
        "err": max(errs), "scale": scale, "compared": len(errs),
        "parity": out["one"]["tokens"] == tp4["tokens"],
        "allreduce": [tp4["allreduce"], out["one"]["allreduce"]],
        "analytic": analytic, "steps": len(tp4["decode"]),
        "buckets": sorted(plen for _, plen, _ in tp4["prefills"]),
        "admit_compiles": [tp4["admit_compiles"],
                           out["one"]["admit_compiles"]]}))
""")


def test_yi_tensor_parallel_engine_matches_float32_reference():
    """yi-9b's block on a data=1,model=4 mesh of four CPU devices, served
    by the continuous engine with the Pallas kernels per shard: the
    admission logits and every decode step's logits through the paged
    cache agree with a plain float32 forward of the served sequence, and
    ``allreduce_bytes`` is two f32 [rows, d_model] partial sums per layer
    per model step (0 on one device).

    Tolerance 1e-5 of the largest reference logit: the program and the
    reference both compute in float32 and differ only in the order of sums
    (the kernels' K blocks, four partial sums added by the all-reduce, the
    flash kernel's online softmax), which read 8.4e-7 of that scale here.
    Rounding the weights alone to bfloat16 moves the reference's logits by
    9.1e-3 of it, so a program a precision below float32 fails."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _YI_TP4],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    rec = json.loads(line[len("RESULT "):])
    assert rec["buckets"] == [8, 16, 32], rec
    # one admission program per bucket, however a drain starts: the run's
    # PRNG key enters every program with the layout programs hand it on
    assert rec["admit_compiles"] == [3, 3], rec
    assert rec["compared"] == 3 + 5 + 8 + 4, rec
    assert rec["err"] <= 1e-5 * rec["scale"], rec
    assert rec["parity"], rec
    # every admission and decode step on the mesh; none on one device
    assert rec["allreduce"] == [rec["analytic"], 0], rec
    # 9 tokens need 8 steps; the chunk in which the last row ends stops
    # at its last token
    assert rec["steps"] == 8, rec
