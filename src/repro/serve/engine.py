"""Device-resident continuous-batching serve engine.

Production-shaped serving over a fixed pool of ``max_batch`` KV-cache slots,
with two schedulers sharing one model path:

* **Continuous (default)** — true continuous batching over a *paged* KV
  cache: capacity is measured in tokens, finished rows are evicted at chunk
  boundaries mid-decode, and queued requests are prefilled and admitted into
  freed slots without restarting the fused loop.  Each live request holds a
  block table over fixed-size pages (``page_size`` is the tuned
  ``paged_attn`` knob); per decode chunk the engine gathers every row's KV
  into a dense right-aligned view, runs the same fused loop the wave path
  runs, and scatters the chunk's new KV columns back to their pages — so
  the model source never sees a page table and token-for-token parity with
  the wave engine holds by construction.  Host bookkeeping (allocator,
  block tables, FIFO admission, youngest-first preemption) lives in
  :mod:`repro.serve.kv_pages`.
* **Wave (``ServeConfig(scheduler="wave")``)** — requests are admitted into
  free slots and evicted on completion; the KV cache is allocated once per
  engine and reused across ``generate`` calls (stale entries are never
  attended thanks to per-slot ``kv_start``/length masking).  More requests
  than slots are served in successive waves.  Attention-free (pure SSM) and
  int8-quantized caches always take this path.
* **Fused decode loop** — a single ``jax.lax.while_loop`` carries tokens,
  per-slot done flags, per-slot token budgets, EOS checks, the sampling key
  and the KV cache entirely on device.  Exactly ONE ``jax.device_get`` per
  decode wave — i.e. per ``generate`` call whenever the batch fits the slot
  pool — fetches the finished token buffer; no per-token host round-trips.
* **Ragged batches** — prompts are right-aligned (left-padded); the per-slot
  pad offset ``kv_start`` is threaded through the model so attention masks
  pad columns, RoPE/learned positions restart at each row's first real
  token, and SSM blocks zero pad contributions.  Each row therefore decodes
  exactly what it would decode alone.
* **Tuned tiles** — the decode step's GEMM shapes are traced once and
  resolved against the global tile registry; the lookup provenance
  (exact/nearest/generic/default) is surfaced in :meth:`Engine.stats`.
* **Meshes** — ``ServeConfig(mesh="data=4,model=2")`` (or an ambient
  ``distributed.ctx.use_mesh``) shards params, KV-cache slots and the batch
  by the ``ShardingRules`` of the mesh — the distribution layer's analogue
  of the paper's tuning table: the same engine source serves one chip or a
  pod, selected by a spec string.  Tuned-tile lookups are then keyed on the
  per-shard *local* GEMM shapes (TP changes which tuned entry is hit), and
  :meth:`Engine.stats` reports mesh/sharding provenance.

* **Prefix cache** — continuous engines reuse prefilled prompt KV across
  requests (:mod:`repro.serve.prefix_cache`): a trie of page-sized token
  chunks pins pages in the allocator with refcounts.  A full-prompt hit
  skips admission prefill entirely (shared read-only pages + one
  copy-on-write page at the divergence point + a cached logits/fixed-state
  snapshot — bit-exact under greedy decoding); a page-aligned partial hit
  shares the prefix pages and redirects the re-run prefill's shared-column
  writes to the TRASH page.  Eviction is LRU under pool pressure and always
  yields before live rows are preempted.
* **Typed API** — :mod:`repro.serve.api`: ``submit(Request) ->
  RequestHandle`` and ``run() -> List[GenerationResult]`` with per-request
  timing, finish reasons, prefix provenance and per-token ``stream``
  callbacks fired at each decode-chunk boundary.  The legacy positional
  ``submit(prompt, n)`` / ``{rid: tokens}`` surface still works behind one
  ``DeprecationWarning`` per process.

Prompt lengths are bucketed to powers of two (min 8, clamped so the bucket
plus the wave's decode budget never exceeds ``max_len``) so a wave and a
lone prompt in the same bucket share one compiled prefill *and* take
bit-identical float paths — the basis of the ragged-batch parity guarantee.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged import paged_copy, paged_gather, paged_scatter
from repro.models.model import Model
from repro.serve import api
from repro.serve.stats_schema import SCHEMA_VERSION

_PLEN_BUCKET_MIN = 8

#: one DeprecationWarning per process for the legacy submit()/run() surface
_LEGACY_SUBMIT_WARNED = False

#: per-request latency records kept for percentile stats
_LATENCY_WINDOW = 4096


def _percentiles(xs: List[float]) -> Dict[str, Optional[float]]:
    if not xs:
        return {"p50": None, "p95": None, "p99": None}
    q = np.percentile(np.asarray(xs, np.float64), [50.0, 95.0, 99.0])
    return {"p50": float(q[0]), "p95": float(q[1]), "p99": float(q[2])}


def _bucket_len(n: int, cap: Optional[int] = None) -> int:
    """Smallest power-of-two bucket >= ``n``, clamped to ``cap``.

    The clamp keeps near-capacity buckets inside the KV-slot capacity
    instead of overshooting ``max_len`` and forcing callers back to exact
    (per-length-recompiling) sizes.  When ``cap < n`` the cap itself is
    returned (< n) and the caller must fall back to exact sizing.
    """
    b = _PLEN_BUCKET_MIN
    while b < n:
        b *= 2
    if cap is not None and b > cap:
        b = cap
    return b


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8                # KV-cache slots
    max_len: int = 512                # per-slot cache capacity (prompt + new)
    temperature: float = 0.0          # 0 => greedy
    eos_token: Optional[int] = None
    seed: int = 0
    profile: bool = False             # block after prefill to split timings
    # Hardware profile the engine tunes against (registry key).  None uses
    # the ambient execution context's resolution: explicit override >
    # $REPRO_HARDWARE > jax.devices() detection.
    hardware: Optional[str] = None
    # Device mesh: a spec string ("data=4,model=2" | "auto"), a prebuilt
    # jax.sharding.Mesh, or None.  None picks up the ambient
    # distributed.ctx.use_mesh() topology (single-device when absent).
    mesh: Optional[Union[str, jax.sharding.Mesh]] = None
    # Tokens decoded per fused-loop iteration.  Every while-loop spin is a
    # cross-device sync point on a mesh, so fatter iterations hide dispatch
    # latency.  None resolves: mesh-keyed tuned entry (decode_loop in the
    # TuningDB, topology in the key) > heuristic (4 on a mesh, 1 alone).
    decode_unroll: Optional[int] = None
    # "continuous" (paged KV, admit/evict at chunk boundaries) or "wave".
    # Pure-SSM and int8-KV models silently run "wave" either way.
    scheduler: str = "continuous"
    # Paged-KV page size in tokens.  None resolves a tuned ``paged_attn``
    # entry keyed by (max_batch, max_len) + hardware + mesh label.
    page_size: Optional[int] = None
    # Paged-pool capacity in TOKENS (the continuous scheduler's admission
    # currency).  None = max_batch * max_len — the wave engine's footprint,
    # now shared by need instead of reserved per slot.
    capacity_tokens: Optional[int] = None
    # Tokens decoded per fused chunk between scheduling boundaries
    # (admission/eviction happen only at boundaries).  Power of two.
    decode_chunk: int = 8
    # Share prefilled prompt KV across requests with common prefixes
    # (continuous scheduler only; requests served with extra_inputs are
    # never cached — their extras aren't part of the content key).
    prefix_cache: bool = True


@dataclasses.dataclass
class _Request:
    rid: int
    prompt: List[int]
    max_new: int
    row: Optional[int] = None         # row in the shared extra_inputs arrays
    slot: Optional[int] = None
    tokens: Optional[List[int]] = None
    # -- typed-API bookkeeping ------------------------------------------
    legacy: bool = False              # submitted via the deprecated surface
    handle: Optional[api.RequestHandle] = None
    stream: Optional[Callable[[api.StreamEvent], None]] = None
    result: Optional[api.GenerationResult] = None
    finish_reason: Optional[str] = None
    # Stamps on the engine's clock (time.perf_counter): the caller's submit
    # (Server.submit, else Engine.submit), the engine's ingest, admission.
    t_submit: float = 0.0
    t_ingest: float = 0.0
    t_admit: Optional[float] = None
    t_first: Optional[float] = None   # first token host-visible (TTFT end)
    prefix_hit: Optional[str] = None  # "full" | "partial" | None
    cached_prefix_tokens: int = 0


class _SlotScheduler:
    """Admit/evict bookkeeping over the fixed pool of KV-cache slots."""

    def __init__(self, n_slots: int):
        self.n_slots = n_slots
        self._free = list(range(n_slots))
        self._use_count = [0] * n_slots
        self.admitted = 0
        self.evicted = 0

    def admit(self, req: _Request) -> int:
        if not self._free:
            raise RuntimeError("no free KV-cache slot")
        slot = self._free.pop(0)
        req.slot = slot
        self._use_count[slot] += 1
        self.admitted += 1
        return slot

    def evict(self, req: _Request) -> None:
        self._free.append(req.slot)
        self._free.sort()
        self.evicted += 1

    @property
    def reuses(self) -> int:
        return sum(max(c - 1, 0) for c in self._use_count)


class Engine:
    """Continuous-batching engine over a fixed slot pool.

    ``generate`` is the batched entry point; ``submit``/``run`` expose the
    underlying request queue for callers that stream requests in.
    """

    def __init__(self, model: Model, params, cfg: ServeConfig):
        from repro.core import current_hardware
        from repro.core.hardware import find_profile, resolve_hardware
        self.model = model
        self.params = params
        self.cfg = cfg
        # Resolved once at engine construction so every tile lookup (and the
        # stats provenance) is pinned to one profile for the engine's life.
        self.hardware = (resolve_hardware(cfg.hardware) if cfg.hardware
                         else current_hardware())
        prof = find_profile(self.hardware)
        self._platform = prof.platform if prof else "unknown"
        # Mesh topology: explicit config > ambient use_mesh() > single-device.
        # Resolved once, like the hardware profile — one engine, one mesh.
        from repro.distributed import ctx as dctx
        mesh, rules = cfg.mesh, None
        if mesh is None:
            mesh, rules = dctx.current_mesh(), dctx.current_rules()
        if isinstance(mesh, str):
            from repro.launch.mesh import build_mesh
            mesh = build_mesh(mesh)
        self.mesh = mesh
        self.rules = None
        # Rows per admission prefill call: one per shard of the batch axes
        # (1 single-device), so a call carries the rows it admits and not
        # every slot.
        self._admit_rows = 1
        if mesh is not None:
            from repro.distributed import sharding as sh
            # Inference rules (TP only, no FSDP); explicit ambient rules
            # still win for callers that know better.
            self.rules = rules or sh.serving_rules(mesh)
            self._admit_rows = sh.axis_size(mesh, self.rules.batch_axes)
            # Re-place params by the rules (no-op when they were initialised
            # by the same rules; values unchanged either way, so sharded and
            # single-device engines stay token-for-token equal).
            self.params = sh.shard_params(params, mesh, self.rules,
                                          model.template)
        self._prefill = jax.jit(self._with_mesh(model.prefill))
        self._loop = None                 # built lazily (per-engine closure)
        self._unroll: Optional[int] = None         # resolved lazily, cached
        self._unroll_source: Optional[str] = None
        self._cache = None                # allocated once, reused across calls
        self._sched = _SlotScheduler(cfg.max_batch)
        self._queue: List[_Request] = []
        self._next_rid = 0
        self._tile_lookups: Optional[Dict[str, Dict[str, object]]] = None
        self._prefill_flash_lookups: Dict[str, Dict[str, object]] = {}
        self._plen_buckets: set = set()
        # -- continuous-batching state (paged KV pool) -------------------
        if cfg.scheduler not in ("continuous", "wave"):
            raise ValueError(f"unknown scheduler {cfg.scheduler!r}; "
                             f"expected 'continuous' or 'wave'")
        chunk = int(cfg.decode_chunk)
        if chunk < 1 or chunk & (chunk - 1):
            raise ValueError(
                f"decode_chunk must be a power of two >= 1, got {chunk}")
        self._chunk = chunk
        self._scheduler = cfg.scheduler
        self._scheduler_forced: Optional[str] = None
        if cfg.scheduler == "continuous":
            # The paged pool holds "self"-attention KV; models without one
            # (pure SSM) or with a quantized {q, s} cache layout keep the
            # dense wave path — transparently, so callers never branch.
            if model.cfg.family == "ssm":
                self._scheduler = "wave"
                self._scheduler_forced = "no self-attention KV cache"
            elif model.cfg.kv_quant:
                self._scheduler = "wave"
                self._scheduler_forced = "int8-quantized KV cache"
        self._capacity_tokens = int(cfg.capacity_tokens
                                    or cfg.max_batch * cfg.max_len)
        self._page_size: Optional[int] = None
        self._page_size_source: Optional[str] = None
        self._alloc = None                # PageAllocator (continuous only)
        self._csched = None               # ContinuousScheduler
        self._pools = None                # paged "self" KV leaves (flat)
        self._fixed = None                # resident non-paged cache leaves
        self._cur = None                  # (max_batch,) next-token register
        self._scratch: Dict[int, object] = {}   # admission prefill caches
        self._chunk_fn = None             # jitted fused chunk (lazily built)
        self._admit_fn = None             # jitted prefill+insert
        self._copy_fn = None              # jitted COW page copy
        self._prefix = None               # PrefixCache (continuous only)
        # Server-mode ingestion: a callable polled at every chunk/wave
        # boundary yielding (api.Request, RequestHandle, t_submit) triples
        # submitted mid-drain (see repro.serve.server.Server).
        self._ingest_hook: Optional[Callable] = None
        self._admit_passes = 0            # admission passes (admit_id)
        # Explicit all-reduce bytes (core.allreduce_tally), counted when a
        # program is traced: per admission run by plen bucket, per decode
        # step by view width; their sum over the runs made so far.
        self._admit_allreduce: Dict[int, int] = {}
        self._step_allreduce: Dict[int, int] = {}
        self._allreduce_bytes = 0
        self._lat_ttft: List[float] = []  # finished-request TTFT records
        self._lat_tok: List[float] = []   # finished-request tok/s records
        self._stats: Dict[str, float] = {
            "requests": 0, "tokens_generated": 0, "generate_calls": 0,
            "waves": 0, "chunks": 0, "admission_prefills": 0,
            "device_transfers": 0, "cache_allocs": 0,
            "prefill_seconds": 0.0, "decode_seconds": 0.0,
            "total_seconds": 0.0,
        }

    # -- mesh plumbing --------------------------------------------------
    def _with_mesh(self, fn):
        """Wrap ``fn`` so tracing happens under this engine's activation
        policy (``constrain`` pins residual/logits layouts to the mesh).
        Identity when the engine is single-device."""
        if self.mesh is None:
            return fn
        mesh, rules = self.mesh, self.rules

        @functools.wraps(fn)      # keeps the program's name (jit_<fn>)
        def wrapped(*args, **kwargs):
            from repro.distributed.ctx import activation_policy
            with activation_policy(mesh, rules):
                return fn(*args, **kwargs)

        return wrapped

    def _place_batch(self, tree):
        """Shard leading-batch-dim arrays over the data axes (no-op
        single-device).  Values are unchanged — only the layout."""
        if self.mesh is None:
            return tree
        from repro.distributed import sharding as sh
        return jax.device_put(
            tree, sh.batch_shardings(self.mesh, self.rules, tree))

    # -- sampling ------------------------------------------------------
    def _sample(self, logits: jax.Array, key) -> jax.Array:
        if self.cfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / self.cfg.temperature, axis=-1).astype(jnp.int32)

    # -- fused device-resident decode loop -----------------------------
    def _resolve_unroll(self) -> int:
        """Tokens decoded per fused-loop iteration.

        Resolution: explicit ``ServeConfig.decode_unroll`` > a mesh-keyed
        ``decode_loop`` tuned entry (the topology is part of the op key, so
        ``data=4,model=2`` can tune a different unroll than a single chip) >
        the heuristic (4 on a mesh — spin sync points are collectives there
        — else 1).  The resolved value and its provenance land in
        :meth:`stats` as ``decode_unroll`` / ``decode_unroll_source``.
        """
        if self._unroll is not None:
            return self._unroll
        if self.cfg.decode_unroll is not None:
            self._unroll = max(int(self.cfg.decode_unroll), 1)
            self._unroll_source = "config"
        else:
            from repro.core.registry import GLOBAL_REGISTRY, OP_DECODE_LOOP
            from repro.distributed.sharding import mesh_axis_label
            res = GLOBAL_REGISTRY.lookup_op(
                OP_DECODE_LOOP, self.hardware, self.model.cfg.dtype,
                (self.cfg.max_batch, self.cfg.max_len),
                mesh=mesh_axis_label(self.mesh))
            if res.source in ("exact", "nearest", "generic"):
                self._unroll = max(int(res.config.unroll), 1)
                self._unroll_source = f"tuned:{res.source}"
            else:
                self._unroll = 4 if self.mesh is not None else 1
                self._unroll_source = "heuristic"
        return self._unroll

    def _build_loop(self):
        decode = self.model.decode_step
        eos = self.cfg.eos_token

        def loop(params, cache, logits0, key, kv_start, budget, offset0, *,
                 width: int, unroll: int):
            b = logits0.shape[0]
            # Split BEFORE the first sample: the parent key is reserved for
            # splitting only, so the first token is uncorrelated with later
            # ones.
            key, sub = jax.random.split(key)
            cur = self._sample(logits0, sub)
            done = budget <= 0                 # empty slots start finished
            buf = jnp.zeros((b, width), jnp.int32)
            lens = jnp.zeros((b,), jnp.int32)

            # ``alldone`` rides in the carry so the while cond is a plain
            # scalar read.  Evaluating ``done.all()`` inside cond (and again
            # inside body's predicate) costs a cross-device reduction per
            # spin when ``done`` picks up a batch sharding — two extra
            # blocking collectives per token that serialize the mesh decode
            # loop.  Computing it ONCE per body and carrying the scalar
            # keeps every control decision local.
            def cond(carry):
                step, cur, done, alldone, buf, lens, cache, offset, key = carry
                return (step < width) & ~alldone

            def body(carry):
                step, cur, done, alldone, buf, lens, cache, offset, key = carry
                # Unrolled body: each while iteration records + decodes
                # ``unroll`` tokens.  Every loop spin is a cross-device sync
                # point on a mesh (cond broadcast + per-device dispatch), so
                # fewer, fatter iterations hide that latency behind compute;
                # done/budget bookkeeping stays exact per token via the
                # masked buffer writes.
                for _ in range(unroll):
                    with jax.named_scope("decode_token"):
                        buf = jax.lax.dynamic_update_slice(
                            buf, jnp.where(done, 0, cur)[:, None], (0, step))
                        lens = lens + jnp.where(done, 0, 1).astype(jnp.int32)
                        if eos is not None:
                            done = done | (cur == eos)
                        done = done | (lens >= budget)
                        alldone = done.all()
                        step = step + 1

                        def advance(op):
                            cache, cur, key, offset = op
                            key, sub = jax.random.split(key)
                            logits, cache = decode(params, cur[:, None],
                                                   cache, offset, kv_start)
                            return (cache, self._sample(logits, sub), key,
                                    offset + 1)

                        # Skip the model step once every live slot finished.
                        cache, cur, key, offset = jax.lax.cond(
                            (step < width) & ~alldone, advance, lambda op: op,
                            (cache, cur, key, offset))
                return (step, cur, done, alldone, buf, lens, cache, offset,
                        key)

            carry = (jnp.int32(0), cur, done, done.all(), buf, lens, cache,
                     offset0, key)
            _, _, _, _, buf, lens, cache, _, _ = jax.lax.while_loop(
                cond, body, carry)
            return buf, lens, cache

        return jax.jit(self._with_mesh(loop),
                       static_argnames=("width", "unroll"))

    # -- slot-pool cache -----------------------------------------------
    def _ensure_cache(self):
        if self._cache is None:
            cache = self.model.init_cache(self.cfg.max_batch,
                                          self.cfg.max_len)
            if self.mesh is not None:
                # Shard the slot pool itself: batch over the data axes,
                # heads (or cache sequence, for GQA) over the tensor axis.
                from repro.distributed import sharding as sh
                cache = jax.device_put(
                    cache, sh.cache_shardings(self.mesh, self.rules, cache))
            self._cache = cache
            self._stats["cache_allocs"] += 1
            self._trace_decode_tiles()
        return self._cache

    def _trace_decode_tiles(self) -> None:
        """Abstractly trace one decode step, resolve its GEMM shapes against
        the tuned-tile registry, and record the lookup provenance.

        On a mesh the traced shapes are *global*; what each shard actually
        runs is the local GEMM, split as ``matmul`` splits it for the
        per-shard kernel, so the registry lookup is keyed on the local
        shape that ``matmul`` reports (TP therefore changes which tuned
        entry is hit).  Both shapes are recorded in the provenance.
        """
        from repro.core import capture_gemm_shapes
        from repro.core.registry import GLOBAL_REGISTRY, OP_GEMM
        from repro.distributed.sharding import mesh_axis_label
        b = self.cfg.max_batch
        tok = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        off = jax.ShapeDtypeStruct((), jnp.int32)
        ks = jax.ShapeDtypeStruct((b,), jnp.int32)
        cache = self._cache
        if cache is None:      # continuous engines never build a dense pool
            cache = jax.eval_shape(
                lambda: self.model.init_cache(b, self.cfg.max_len))
        try:
            with capture_gemm_shapes(per_shard=True) as calls:
                jax.eval_shape(self._with_mesh(self.model.decode_step),
                               self.params, tok, cache, off, ks)
        except Exception:      # provenance is telemetry, never fatal
            self._tile_lookups = {}
            return
        # distinct weights can shard one global (K, N) differently (e.g.
        # square wq vs wo); record a lookup per local variant
        variants: Dict[tuple, set] = {}
        for shape, local in calls:
            variants.setdefault(shape, set()).add(local)
        mesh_label = mesh_axis_label(self.mesh)
        lookups = {}
        for (m, k, n), locals_ in sorted(variants.items()):
            for lm, lk, ln in sorted(locals_):
                res = GLOBAL_REGISTRY.lookup_op(
                    OP_GEMM, self.hardware, self.model.cfg.dtype,
                    (lm, lk, ln), mesh=mesh_label)
                entry = {
                    "source": res.source,
                    "tile": res.config.label,
                    "matched_shape": res.matched_shape,
                }
                key = f"{m}x{k}x{n}"
                if self.mesh is not None:
                    entry["local_shape"] = f"{lm}x{lk}x{ln}"
                    entry["mesh"] = res.mesh
                    if len(locals_) > 1:
                        key = f"{m}x{k}x{n}->{lm}x{lk}x{ln}"
                lookups[key] = entry
        self._tile_lookups = lookups

    def _record_prefill_flash_tiles(self, plen: int) -> None:
        """Resolve the tuned flash-attention blocks this prefill bucket uses
        and record the lookup provenance (mirrors the decode GEMM trace).

        The model path performs the same lookup inside ``layers.attention``
        (via :func:`repro.core.attention_api.flash_attention`); re-resolving
        here keeps the telemetry identical without threading state through
        jitted code.
        """
        cfg = self.model.cfg
        if cfg.attention_impl != "flash" or not cfg.num_heads:
            return
        key = f"{plen}x{plen}x{cfg.resolved_head_dim}"
        if key in self._prefill_flash_lookups:
            return
        from repro.core.attention_api import flash_tile_lookup
        res = flash_tile_lookup(self.hardware, cfg.dtype, plen, plen,
                                cfg.resolved_head_dim)
        self._prefill_flash_lookups[key] = {
            "source": res.source,
            "tile": res.config.label,
            "matched_shape": res.matched_shape,
        }

    # -- paged KV pool (continuous scheduler) ----------------------------
    def _resolve_page_size(self) -> None:
        """Page size (tokens) for the paged pool: explicit config > tuned
        ``paged_attn`` entry keyed by (max_batch, max_len) + hardware +
        mesh label > registry fallback.  Provenance lands in stats()."""
        if self._page_size is not None:
            return
        if self.cfg.page_size is not None:
            page = max(int(self.cfg.page_size), 1)
            self._page_size_source = "config"
        else:
            from repro.core.registry import GLOBAL_REGISTRY, OP_PAGED_ATTN
            from repro.distributed.sharding import mesh_axis_label
            res = GLOBAL_REGISTRY.lookup_op(
                OP_PAGED_ATTN, self.hardware, self.model.cfg.dtype,
                (self.cfg.max_batch, self.cfg.max_len),
                mesh=mesh_axis_label(self.mesh))
            page = max(int(res.config.page_size), 1)
            self._page_size_source = (
                f"tuned:{res.source}"
                if res.source in ("exact", "nearest", "generic")
                else res.source)
        self._page_size = min(page, self._capacity_tokens)

    def _ensure_pool(self):
        """Allocate the paged pool once per engine: flat token-axis buffers
        for every "self" KV leaf plus a resident tree for the fixed-size
        leaves (cross-KV, SSM/conv states) that admission row-scatters."""
        if self._pools is not None:
            return
        from repro.serve import kv_pages
        self._resolve_page_size()
        self._alloc = kv_pages.PageAllocator(self._capacity_tokens,
                                             self._page_size)
        self._csched = kv_pages.ContinuousScheduler(self.cfg.max_batch,
                                                    self._alloc)
        npp = self._alloc.num_pages * self._page_size
        template = self.model.init_cache(self.cfg.max_batch, 1)

        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        from repro.distributed import sharding as sh
        mesh = self.mesh
        ta = self.rules.tensor_axis if mesh is not None else None

        def pool_leaf(leaf):
            # (lead..., B, 1, kvh, hd) -> (lead..., num_pages*page, kvh, hd).
            # On a mesh each device makes its own shard: a whole pool made
            # on one device and then split would hold it twice there (yi-9b
            # on four chips: 4.8 GB beside 4.4 GB of weights).
            shape = leaf.shape[:-4] + (npp,) + leaf.shape[-2:]
            sharding = None
            if mesh is not None:
                # no batch dim on the flat pool: shard KV heads over the
                # tensor axis when divisible, replicate otherwise
                spec = [None] * len(shape)
                if ta and shape[-2] % sh.axis_size(mesh, ta) == 0:
                    spec[-2] = ta
                sharding = NamedSharding(mesh, P(*spec))
            return jnp.zeros(shape, leaf.dtype, device=sharding)

        pools = jax.tree_util.tree_map(pool_leaf, template["self"])
        fixed = {k: v for k, v in template.items() if k != "self"}
        if mesh is not None and fixed:
            fixed = jax.device_put(
                fixed, sh.cache_shardings(mesh, self.rules, fixed))
        self._pools, self._fixed = pools, fixed
        self._cur = jnp.zeros((self.cfg.max_batch,), jnp.int32)
        if self.mesh is not None:
            # replicated from the start, as every later admission leaves it:
            # a first call with another layout compiles admission twice
            self._cur = jax.device_put(self._cur,
                                       NamedSharding(self.mesh, P()))
        if self.cfg.prefix_cache:
            from repro.serve.prefix_cache import PrefixCache
            self._prefix = PrefixCache(self._alloc)
            # Under pool pressure the scheduler reclaims cache-pinned pages
            # (LRU) before preempting live rows.
            self._csched.reclaim = self._prefix.reclaim
        self._stats["cache_allocs"] += 1
        self._trace_decode_tiles()

    def _scratch_cache(self, plen: int):
        """Admission prefill cache for one plen bucket, one row per row of
        a prefill call, reused across admissions: prefill fully overwrites
        its "self" columns [0, plen) and recomputes every fixed leaf, so
        stale contents never leak."""
        cache = self._scratch.get(plen)
        if cache is None:
            cache = self.model.init_cache(self._admit_rows, plen)
            if self.mesh is not None:
                from repro.distributed import sharding as sh
                cache = jax.device_put(
                    cache, sh.cache_shardings(self.mesh, self.rules, cache))
            self._scratch[plen] = cache
        return cache

    @staticmethod
    def _scatter_fixed(fixed, new, slot_map):
        """Row-scatter ``new``'s rows into the resident fixed tree along
        each leaf's batch dim (kind-aware: cross-KV at -4, SSM state at -4,
        conv state at -3): row i goes to slot ``slot_map[i]``.  ``slot_map``
        pads with an out-of-range index, which JAX scatters drop."""
        kinds = {"cross": "kv", "ssm": "ssm", "conv": "conv"}

        def walk(old, upd, kind=None):
            if isinstance(old, dict):
                return {k: walk(old[k], upd[k], kinds.get(k, kind))
                        for k in old}
            if isinstance(old, (tuple, list)):
                return type(old)(walk(o, u, kind)
                                 for o, u in zip(old, upd))
            bd = old.ndim - (3 if kind == "conv" else 4)
            o2 = jnp.moveaxis(old, bd, 0)
            u2 = jnp.moveaxis(upd, bd, 0)
            return jnp.moveaxis(o2.at[slot_map].set(u2), 0, bd)

        return walk(fixed, new)

    def _build_admit_fn(self):
        """Jitted admission: one prefill of a call's packed rows into the
        plen-bucket scratch cache, prompt KV scattered to its pages, fixed
        leaves row-scattered to their slots, first token sampled into
        ``cur``.  The row count is fixed per engine, so this compiles once
        per plen bucket (shapes carry the key)."""
        from repro.core import allreduce_tally
        prefill = self.model.prefill

        def admit_fn(params, batch, scratch, pools, fixed, cur, key,
                     dest_idx, slot_map):
            with allreduce_tally() as tally:
                logits0, filled = prefill(params, batch, scratch)
            self._admit_allreduce[batch["tokens"].shape[1]] = tally.bytes
            pools_out = jax.tree_util.tree_map(
                lambda pool, src: paged_scatter(pool, dest_idx, src),
                pools, filled["self"])
            fixed_out = self._scatter_fixed(
                fixed, {k: filled[k] for k in fixed}, slot_map)
            # Split BEFORE the first sample (wave-loop key discipline).
            key, sub = jax.random.split(key)
            first = self._sample(logits0, sub)
            cur_out = cur.at[slot_map].set(first)
            # logits0 rides out so admission can snapshot each admitted
            # row's last-position logits into the prefix cache.
            return pools_out, fixed_out, cur_out, key, logits0

        return jax.jit(self._with_mesh(admit_fn))

    # -- prefix-cache device plumbing ------------------------------------
    @staticmethod
    def _walk_fixed(tree, fn, kind=None):
        """Apply ``fn(leaf, kind)`` over a fixed-cache tree with the same
        kind resolution ``_scatter_fixed`` uses (cross-KV / SSM state at
        batch dim -4, conv state at -3)."""
        kinds = {"cross": "kv", "ssm": "ssm", "conv": "conv"}
        if isinstance(tree, dict):
            return {k: Engine._walk_fixed(v, fn, kinds.get(k, kind))
                    for k, v in tree.items()}
        if isinstance(tree, (tuple, list)):
            return type(tree)(Engine._walk_fixed(v, fn, kind) for v in tree)
        return fn(tree, kind)

    def _slice_fixed_row(self, slot: int):
        """Snapshot one slot's rows of every fixed cache leaf (the
        per-request state a full prefix hit must restore — SSM/conv state
        for hybrids; empty for pure transformers)."""
        def take(leaf, kind):
            bd = leaf.ndim - (3 if kind == "conv" else 4)
            return jnp.take(leaf, slot, axis=bd)
        return self._walk_fixed(self._fixed, take)

    def _restore_fixed_row(self, fixed, snap, slot: int):
        """Write a :meth:`_slice_fixed_row` snapshot back into ``slot``."""
        kinds = {"cross": "kv", "ssm": "ssm", "conv": "conv"}

        def walk(old, sn, kind=None):
            if isinstance(old, dict):
                return {k: walk(old[k], sn[k], kinds.get(k, kind))
                        for k in old}
            if isinstance(old, (tuple, list)):
                return type(old)(walk(o, s, kind)
                                 for o, s in zip(old, sn))
            bd = old.ndim - (3 if kind == "conv" else 4)
            moved = jnp.moveaxis(old, bd, 0)
            return jnp.moveaxis(moved.at[slot].set(sn), 0, bd)

        return walk(fixed, snap)

    def _build_copy_fn(self):
        """Jitted COW page copy: page ids are traced scalars, so every
        divergence-point copy shares one compile."""
        page = self._page_size

        def copy_fn(pools, src_page, dst_page):
            return jax.tree_util.tree_map(
                lambda pool: paged_copy(pool, src_page, dst_page, page),
                pools)

        return jax.jit(self._with_mesh(copy_fn))

    def _restore_hits(self, hits, key: jax.Array) -> jax.Array:
        """Admit full-prompt prefix hits without prefill: the row's block
        table already points at the shared pages; copy the straddling page
        (COW), restore the fixed-leaf snapshot, and sample the first token
        from the cached last-position logits (bit-identical under greedy —
        the argmax runs over the exact array the cold path sampled from)."""
        from repro.profiling import annotate
        t0 = time.perf_counter()
        page = self._page_size
        with annotate("serve.prefix_restore"):
            for req, row, entry in hits:
                if entry.tail_page is not None:
                    dst = row.pages[len(req.prompt) // page]
                    if self._copy_fn is None:
                        self._copy_fn = self._build_copy_fn()
                    self._pools = self._copy_fn(
                        self._pools, jnp.int32(entry.tail_page),
                        jnp.int32(dst))
                if self._fixed:
                    self._fixed = self._restore_fixed_row(
                        self._fixed, entry.fixed, row.slot)
                # Same key discipline as admission: split, then sample.
                key, sub = jax.random.split(key)
                first = self._sample(entry.logits0[None, :], sub)
                self._cur = self._cur.at[row.slot].set(first[0])
        self._stats["prefill_seconds"] += time.perf_counter() - t0
        return key

    def _build_chunk_fn(self):
        """Jitted fused decode chunk: gather a dense right-aligned KV view
        from the paged pool, run the wave-style fused loop for ``chunk``
        tokens, scatter the chunk's new KV columns back to their pages.

        One deliberate difference from the wave loop: the wave loop skips
        the *final* advance (nothing reads the last token's KV), while the
        chunk loop always advances while any row is live — the last emitted
        token's KV must land in the pool before the next chunk reads it,
        and the final advance's sample becomes the next chunk's first
        token (carried device-resident in ``cur``).
        """
        from repro.core import allreduce_tally
        decode = self.model.decode_step
        eos = self.cfg.eos_token

        def chunk_fn(params, pools, fixed, cur, key, gidx, sidx, kv_start,
                     budget, *, width: int, chunk: int, unroll: int):
            view = jax.tree_util.tree_map(
                lambda pool: paged_gather(pool, gidx), pools)
            cache = dict(fixed)
            cache["self"] = view
            b = cur.shape[0]
            done = budget <= 0                 # empty slots start finished
            buf = jnp.zeros((b, chunk), jnp.int32)
            lens = jnp.zeros((b,), jnp.int32)

            def cond(carry):
                step, cur, done, alldone, buf, lens, cache, offset, key = carry
                return (step < chunk) & ~alldone

            def body(carry):
                step, cur, done, alldone, buf, lens, cache, offset, key = carry
                for _ in range(unroll):
                    with jax.named_scope("decode_token"):
                        buf = jax.lax.dynamic_update_slice(
                            buf, jnp.where(done, 0, cur)[:, None], (0, step))
                        lens = lens + jnp.where(done, 0, 1).astype(jnp.int32)
                        if eos is not None:
                            done = done | (cur == eos)
                        done = done | (lens >= budget)
                        alldone = done.all()
                        step = step + 1

                        def advance(op):
                            cache, cur, key, offset = op
                            key, sub = jax.random.split(key)
                            with allreduce_tally() as tally:
                                logits, cache = decode(params, cur[:, None],
                                                       cache, offset,
                                                       kv_start)
                            self._step_allreduce[width] = tally.bytes
                            return (cache, self._sample(logits, sub), key,
                                    offset + 1)

                        # No `step < chunk` guard here (see docstring): the
                        # chunk-boundary advance must run while rows live.
                        cache, cur, key, offset = jax.lax.cond(
                            ~alldone, advance, lambda op: op,
                            (cache, cur, key, offset))
                return (step, cur, done, alldone, buf, lens, cache, offset,
                        key)

            carry = (jnp.int32(0), cur, done, done.all(), buf, lens, cache,
                     jnp.int32(width - chunk), key)
            _, cur, _, _, buf, lens, cache, offset, key = jax.lax.while_loop(
                cond, body, carry)
            cols = jax.tree_util.tree_map(
                lambda leaf: jax.lax.slice_in_dim(
                    leaf, width - chunk, width, axis=leaf.ndim - 3),
                cache["self"])
            pools_out = jax.tree_util.tree_map(
                lambda pool, c: paged_scatter(pool, sidx, c), pools, cols)
            fixed_out = {k: v for k, v in cache.items() if k != "self"}
            return pools_out, fixed_out, cur, key, buf, lens, offset

        return jax.jit(self._with_mesh(chunk_fn),
                       static_argnames=("width", "chunk", "unroll"))

    # -- request queue --------------------------------------------------
    def submit(self, request, max_new_tokens: Optional[int] = None,
               row: Optional[int] = None,
               _handle: Optional[api.RequestHandle] = None,
               _t_submit: Optional[float] = None):
        """Queue one generation request.

        The typed surface takes an :class:`repro.serve.api.Request` and
        returns a :class:`repro.serve.api.RequestHandle` resolved the
        moment the request finishes::

            handle = eng.submit(Request(prompt=[5, 9, 2], max_new_tokens=16))
            eng.run()
            tokens = handle.result().tokens

        The legacy positional form ``submit(prompt, max_new_tokens, row=)``
        still returns a bare request id (and makes :meth:`run` return the
        legacy ``{rid: tokens}`` dict) behind one ``DeprecationWarning``
        per process; see ``docs/SERVING.md`` for migration notes.

        ``_handle`` and ``_t_submit`` are internal: server mode creates the
        handle and stamps the submit time on the caller's thread, so
        ``ttft_s``/``total_s`` count the wait before ingest.
        """
        global _LEGACY_SUBMIT_WARNED
        if isinstance(request, api.Request):
            if max_new_tokens is not None or row is not None:
                raise TypeError(
                    "submit(Request) takes no positional max_new_tokens/row "
                    "— set them on the Request")
            if (request.temperature is not None
                    and request.temperature != self.cfg.temperature):
                raise ValueError(
                    f"Request.temperature {request.temperature} != engine "
                    f"ServeConfig.temperature {self.cfg.temperature}; the "
                    f"engine compiles one sampling configuration")
            prompt = list(request.prompt)
            max_new = int(request.max_new_tokens)
            row = request.row
            stream = request.stream
            legacy = False
        else:
            if not _LEGACY_SUBMIT_WARNED:
                _LEGACY_SUBMIT_WARNED = True
                warnings.warn(
                    "Engine.submit(prompt, max_new_tokens) and the "
                    "{rid: tokens} run() return are deprecated; submit a "
                    "repro.serve.api.Request and read GenerationResult "
                    "(docs/SERVING.md has migration notes)",
                    DeprecationWarning, stacklevel=2)
            if max_new_tokens is None:
                raise TypeError(
                    "legacy submit(prompt, max_new_tokens) needs "
                    "max_new_tokens")
            prompt = list(request)
            max_new = int(max_new_tokens)
            stream = None
            legacy = True
        if not prompt:
            raise ValueError("empty prompt: each prompt needs >= 1 token")
        if max_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
        # Per-request capacity check at enqueue time: an oversized request
        # fails fast HERE instead of bricking the batch it lands in later.
        # The continuous scheduler's capacity currency is TOKENS in the
        # paged pool (one request may exceed max_len as long as it fits the
        # pool); the wave scheduler reserves a max_len-column slot.
        if self._scheduler == "continuous":
            if len(prompt) + max_new > self._capacity_tokens:
                raise ValueError(
                    f"prompt ({len(prompt)}) + max_new ({max_new}) "
                    f"exceeds capacity_tokens ({self._capacity_tokens})")
        elif len(prompt) + max_new > self.cfg.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new ({max_new}) exceeds "
                f"max_len ({self.cfg.max_len})")
        rid = self._next_rid
        self._next_rid += 1
        now = time.perf_counter()
        req = _Request(rid, prompt, max_new, row, legacy=legacy,
                       stream=stream, t_ingest=now,
                       t_submit=now if _t_submit is None else _t_submit)
        if not legacy:
            handle = _handle if _handle is not None else api.RequestHandle()
            handle.request_id = rid
            req.handle = handle
        self._queue.append(req)
        self._stats["requests"] += 1
        return rid if legacy else req.handle

    def run(self, extra_inputs: Optional[Dict[str, jax.Array]] = None):
        """Drain the submitted queue.

        Requests are served by the configured scheduler (continuous paged
        batching by default; wave otherwise).  Ragged prompt lengths are
        handled by left-padding + ``kv_start`` masking.  Wave scheduling is
        *packed by capacity*: a wave's KV need is ``max(prompt) +
        max(max_new)`` over its members, so a long-prompt/small-budget
        request and a short-prompt/big-budget request that each fit on
        their own are scheduled into separate waves instead of being
        rejected together.

        Args:
          extra_inputs: optional per-request model inputs (e.g. Whisper
            ``encoder_embeds``) with leading dim indexed by each request's
            ``row=``.

        Returns:
          ``List[GenerationResult]`` in request-id order — unless any
          drained request came through the deprecated positional
          ``submit``, in which case the legacy ``{request_id: token list}``
          dict is returned (handles are still resolved either way).
        """
        from repro.core import execution_context
        # One key per run, split per wave: waves draw decorrelated samples
        # while repeated runs stay deterministic for a fixed seed.
        key = jax.random.PRNGKey(self.cfg.seed)
        if self.mesh is not None:
            # replicated, as every program hands it on: a program first
            # called with the fresh key would compile again for the key
            # a previous program returns
            from jax.sharding import NamedSharding, PartitionSpec
            key = jax.device_put(key, NamedSharding(self.mesh,
                                                    PartitionSpec()))
        # Pin the ambient hardware profile for the whole drain so the model
        # path's tile lookups (traced inside jit) resolve against the same
        # profile the engine reports in stats().
        with execution_context(hardware=self.hardware):
            if self._scheduler == "continuous":
                drained = self._run_continuous(extra_inputs, key)
            else:
                drained = []
                while True:
                    self._poll_ingest()
                    if not self._queue:
                        break
                    wave = self._pack_wave()
                    key, wave_key = jax.random.split(key)
                    self._run_wave(wave, extra_inputs, wave_key)
                    drained.extend(wave)
        if any(r.legacy for r in drained):
            return {r.rid: r.tokens for r in drained}
        return [r.result for r in sorted(drained, key=lambda r: r.rid)]

    def _poll_ingest(self) -> None:
        """Pull server-mode requests in at a scheduling boundary (no-op
        without an ingest hook)."""
        if self._ingest_hook is None:
            return
        from repro.profiling import annotate, recording
        with annotate("serve.ingest") as span:
            items = self._ingest_hook()
            for req, handle, t_submit in items:
                self.submit(req, _handle=handle, _t_submit=t_submit)
            if recording():
                span.set_metadata(n=len(items))

    def _finish_request(self, req: _Request, reason: str,
                        now: float) -> None:
        """Request-granular completion: latency records, the terminal
        stream event, and handle resolution (servers see results without
        waiting for the drain to end)."""
        req.finish_reason = reason
        total = max(now - req.t_submit, 1e-9)
        ttft = (req.t_first - req.t_submit
                if req.t_first is not None else total)
        n = len(req.tokens)
        self._lat_ttft.append(ttft)
        self._lat_tok.append(n / total)
        if len(self._lat_tok) > _LATENCY_WINDOW:
            del self._lat_ttft[:-_LATENCY_WINDOW]
            del self._lat_tok[:-_LATENCY_WINDOW]
        req.result = api.GenerationResult(
            request_id=req.rid, tokens=list(req.tokens),
            finish_reason=reason, prompt_len=len(req.prompt),
            ttft_s=ttft, total_s=total, tok_per_s=n / total,
            prefix_hit=req.prefix_hit,
            cached_prefix_tokens=req.cached_prefix_tokens)
        if req.stream is not None:
            req.stream(api.StreamEvent(req.rid, None, n, finished=True,
                                       finish_reason=reason))
        if req.handle is not None:
            req.handle._set_result(req.result)

    def _pack_wave(self) -> List[_Request]:
        """Pop the next capacity-feasible wave off the queue (FIFO-biased).

        The head request always ships (submit() guaranteed it fits alone);
        later requests join only while the *joint* requirement
        ``max(prompt) + max(max_new)`` stays within ``max_len`` — requests
        that don't fit keep their queue position for a later wave, so mixed
        long-prompt/long-budget traffic never over-rejects.
        """
        wave = [self._queue.pop(0)]
        longest = len(wave[0].prompt)
        need = wave[0].max_new
        i = 0
        while len(wave) < self.cfg.max_batch and i < len(self._queue):
            r = self._queue[i]
            nl = max(longest, len(r.prompt))
            nn = max(need, r.max_new)
            if nl + nn <= self.cfg.max_len:
                wave.append(self._queue.pop(i))
                longest, need = nl, nn
            else:
                i += 1
        return wave

    # -- continuous drain: admit/evict at chunk boundaries ----------------
    def _run_continuous(self, extra_inputs: Optional[Dict[str, jax.Array]],
                        key: jax.Array) -> List[_Request]:
        """Drain the queue with true continuous batching.

        The loop body is one *chunk boundary*: poll the server ingest hook,
        admit every queue-head request that fits (strict FIFO — the head
        blocks), grow live block tables for the next chunk (preempting
        youngest-admitted rows if the pool runs dry; victims requeue at the
        FRONT with a clean restart), run one fused decode chunk, stream its
        tokens, then evict rows that finished inside it.  Exactly one host
        transfer per chunk.  Returns the finished requests.

        Each phase of a pass is a flat host span (``serve.ingest``,
        ``serve.admit.plan``, ``serve.prefix_restore``, ``serve.admit``,
        ``serve.prefix_insert``, ``serve.chunk.plan`` twice: pages, then
        the chunk's indices, ``serve.chunk``, ``serve.chunk.wait``,
        ``serve.emit``; docs/PROFILING.md).
        """
        if extra_inputs and any(r.row is None for r in self._queue):
            raise ValueError(
                "extra_inputs needs every request submitted with row= "
                "(its index into the extra arrays); generate() does this")
        from repro.profiling import annotate
        self._ensure_pool()
        finished: List[_Request] = []
        active: Dict[int, _Request] = {}        # slot -> request
        try:
            while True:
                self._poll_ingest()
                if not (self._queue or active):
                    break
                if self._queue:
                    key = self._admit_batch(active, extra_inputs, key)
                with annotate("serve.chunk.plan"):
                    preempted = self._csched.ensure_chunk_pages(self._chunk)
                    # Requeue victims at the queue front, smallest rid
                    # first, with generated tokens discarded: re-admission
                    # restarts them cleanly (greedy decode makes the restart
                    # exact).
                    for row in sorted(preempted, key=lambda r: r.rid,
                                      reverse=True):
                        req = active.pop(row.slot)
                        self._sched.evict(req)
                        req.tokens = None
                        req.t_first = None
                        req.prefix_hit = None
                        req.cached_prefix_tokens = 0
                        self._queue.insert(0, req)
                if not active:
                    continue        # preemption freed the pool; re-admit
                key, buf_h, lens_h = self._run_chunk(key)
                self._emit(active, buf_h, lens_h, finished)
        except Exception as exc:
            # Free every live row (pages AND slots) so one bad request
            # can't brick the pool for the next call; fail their handles
            # so server-mode waiters aren't stranded.
            for slot in list(active):
                req = active.pop(slot)
                row = self._csched.rows.get(slot)
                if row is not None:
                    self._csched.evict(row)
                self._sched.evict(req)
                if req.handle is not None and not req.handle.done:
                    req.handle._set_error(exc)
            raise
        return finished

    def _admit_batch(self, active: Dict[int, "_Request"],
                     extra_inputs: Optional[Dict[str, jax.Array]],
                     key: jax.Array) -> jax.Array:
        """Admit every queue-head request that fits (slot + prompt pages),
        consult the prefix cache for each, then prefill the misses and
        insert their prompt KV, fixed-leaf rows and first sampled token
        into the live state.

        The misses are prefilled in calls of ``_admit_rows`` rows (one per
        shard of the mesh's batch axes; 1 single-device), sorted by prompt
        length, each call padded to the bucket of its own longest prompt:
        no call computes slots that are not being admitted, beyond the pad
        rows of a short last call on a mesh.

        Prefix-cache composition (all host bookkeeping):

        * the head's cached prefix pages count as *shared* for the
          capacity check — a mostly-cached long prompt admits into a
          nearly-full pool;
        * when the head still doesn't fit, the cache evicts LRU entries
          before admission blocks (matching entries are re-resolved each
          retry — the evicted item may have been the match);
        * full-prompt hits skip the prefill entirely
          (:meth:`_restore_hits`); partial hits prefill the whole prompt
          for exactness but redirect shared-column writes to TRASH;
        * every prefilled prompt (cache enabled, no extras) is inserted
          back into the cache while its pages are known-live.

        While a capture records, each admitted request leaves one
        ``serve.request`` event (its waits before admission) inside
        ``serve.admit.plan``, and each prefill call one ``serve.admit``
        span; all of them carry the pass's ``admit_id``.
        """
        from repro.profiling import annotate, recording
        tracing = recording()
        admit_id = self._admit_passes
        admitted: List[_Request] = []
        hits = []                       # (req, RowState, cache entry)
        caching = self._prefix is not None and not extra_inputs
        with annotate("serve.admit.plan"):
            while self._queue:
                nxt = self._queue[0]
                m = self._prefix.match(nxt.prompt) if caching else None
                shared = list(m.pages) if m is not None else []
                if not self._csched.can_admit(len(nxt.prompt),
                                              shared_pages=len(shared)):
                    # only sacrifice cached pages for a PAGE shortage — a
                    # busy slot frees itself at the next chunk boundary, and
                    # evicting for it would churn the cache to no benefit
                    if (self._csched.free_slots > 0
                            and self._prefix is not None
                            and self._prefix.evict_one()):
                        continue
                    break
                req = self._queue.pop(0)
                req.t_admit = time.perf_counter()
                row = self._csched.admit(req.rid, len(req.prompt),
                                         req.max_new, shared_pages=shared)
                self._sched.admit(req)  # lockstep: same smallest-free slot
                assert req.slot == row.slot
                req.tokens = []
                active[row.slot] = req
                if caching:
                    self._prefix.record_admit(m, len(req.prompt))
                if m is not None:
                    req.prefix_hit = (api.PREFIX_HIT_FULL if m.full
                                      else api.PREFIX_HIT_PARTIAL)
                    req.cached_prefix_tokens = m.tokens
                if m is not None and m.full:
                    hits.append((req, row, m.entry))
                else:
                    admitted.append(req)
                if tracing:
                    with annotate(
                            "serve.request", rid=req.rid, admit_id=admit_id,
                            front_us=round((req.t_ingest - req.t_submit)
                                           * 1e6),
                            queue_us=round((req.t_admit - req.t_ingest)
                                           * 1e6),
                            hit=0 if m is None else 2 if m.full else 1):
                        pass
            g = self._admit_rows
            ordered = sorted(admitted, key=lambda r: len(r.prompt))
            calls = [self._plan_admission(ordered[i:i + g])
                     for i in range(0, len(ordered), g)]
        if admitted or hits:
            self._admit_passes += 1
        if hits:
            key = self._restore_hits(hits, key)
        if not admitted:
            return key

        if self._admit_fn is None:
            self._admit_fn = self._build_admit_fn()
        prefilled = []                  # (call's requests, its logits0)
        for rows, plen, toks, kv_start, dest, slot_map in calls:
            t0 = time.perf_counter()
            with annotate("serve.admit") as span:
                if tracing:
                    span.set_metadata(
                        admit_id=admit_id, rows=len(rows), batch=g,
                        bucket=plen,
                        prompt_tokens=sum(len(r.prompt) for r in rows),
                        cached_tokens=sum(
                            r.cached_prefix_tokens for r in rows
                            if r.prefix_hit == api.PREFIX_HIT_PARTIAL))
                batch = {"tokens": jnp.asarray(toks),
                         "kv_start": jnp.asarray(kv_start)}
                if extra_inputs:
                    idx = jnp.asarray([r.row for r in rows])
                    for name, arr in extra_inputs.items():
                        padded = jnp.zeros((g,) + arr.shape[1:], arr.dtype)
                        batch[name] = padded.at[:len(rows)].set(
                            jnp.asarray(arr)[idx])
                batch = self._place_batch(batch)
                scratch = self._scratch_cache(plen)
                self._record_prefill_flash_tiles(plen)
                self._plen_buckets.add(int(plen))
                (self._pools, self._fixed, self._cur, key,
                 logits0) = self._admit_fn(
                    self.params, batch, scratch, self._pools, self._fixed,
                    self._cur, key, jnp.asarray(dest), jnp.asarray(slot_map))
                allreduce = self._admit_allreduce[plen]
                self._allreduce_bytes += allreduce
                if tracing:
                    span.set_metadata(allreduce_bytes=allreduce)
                if self.cfg.profile:
                    # deliberate sync: profile mode wants the true prefill /
                    # decode wall-time split, not dispatch-pipeline overlap
                    jax.block_until_ready(self._cur)   # analysis: allow(TP001)
            self._stats["prefill_seconds"] += time.perf_counter() - t0
            self._stats["admission_prefills"] += 1
            prefilled.append((rows, logits0))
        if caching:
            # Insert while the rows' pages are known-live: the cache takes
            # its own refs, so the entries outlive the rows.
            with annotate("serve.prefix_insert"):
                for rows, logits0 in prefilled:
                    for i, r in enumerate(rows):
                        row = self._csched.rows[r.slot]
                        self._prefix.insert(r.prompt, row.pages, logits0[i],
                                            self._slice_fixed_row(r.slot))
        return key

    def _plan_admission(self, rows: List[_Request]):
        """Host inputs of one admission prefill call over ``rows`` (at
        most ``_admit_rows``), packed by position in the call: the bucket
        ``plen`` of the longest prompt, right-aligned tokens, per-row
        ``kv_start``, each column's KV destination in the pool, and the
        slot map (the call's i-th row is slot ``slot_map[i]``)."""
        from repro.serve.kv_pages import TRASH_PAGE
        g = self._admit_rows
        page = self._page_size
        plen = _bucket_len(max(len(r.prompt) for r in rows))
        toks = np.zeros((g, plen), np.int32)
        kv_start = np.full((g,), plen, np.int32)
        # Prompt-KV destinations: pad rows of a short call (and pad columns
        # of real rows) write to the TRASH page; real columns map straight
        # into the row's block table.  Columns covered by a partial prefix
        # hit ALSO write to TRASH — their pages are shared read-only with
        # the cache, and the cached KV is already what this prefill would
        # write (pages-written saving, dedup'd pool memory).
        dest = np.broadcast_to(TRASH_PAGE * page + np.arange(plen) % page,
                               (g, plen)).astype(np.int32).copy()
        for i, r in enumerate(rows):
            row = self._csched.rows[r.slot]
            np_prompt = len(r.prompt)
            toks[i, plen - np_prompt:] = r.prompt
            kv_start[i] = plen - np_prompt
            shared_toks = (r.cached_prefix_tokens
                           if r.prefix_hit == api.PREFIX_HIT_PARTIAL else 0)
            logical = np.arange(shared_toks, np_prompt)
            pages = np.asarray(row.pages, np.int64)
            dest[i, plen - np_prompt + shared_toks:] = (
                pages[logical // page] * page + logical % page)
        # slot_map pads with the out-of-range index max_batch: JAX drops it
        # on scatter, so a pad row leaves every live slot untouched.
        slot_map = np.full((g,), self.cfg.max_batch, np.int32)
        slot_map[:len(rows)] = [r.slot for r in rows]
        return rows, plen, toks, kv_start, dest, slot_map

    def _run_chunk(self, key: jax.Array):
        """One fused decode chunk over every live row; returns the updated
        key plus the host copies of the chunk's token buffer and counts
        (the chunk's single device transfer)."""
        from repro.profiling import annotate, recording
        from repro.serve.kv_pages import gather_indices, scatter_indices
        with annotate("serve.chunk.plan"):
            rows = self._csched.rows
            b = self.cfg.max_batch
            chunk = self._chunk
            page = self._page_size
            width = _bucket_len(max(r.length for r in rows.values()) + chunk)
            gidx = gather_indices(rows, b, width, chunk, page)
            sidx = scatter_indices(rows, b, chunk, page)
            kv_start = np.full((b,), width - chunk, np.int32)
            budget = np.zeros((b,), np.int32)
            for slot, row in rows.items():
                kv_start[slot] = width - chunk - row.length
                budget[slot] = row.budget_left
            # The fused loop advances in ``unroll``-token strides; clamp to
            # a divisor of the chunk so the final stride can't overshoot the
            # token buffer (a clamped dynamic_update_slice would silently
            # rewrite the last column).
            unroll = min(self._resolve_unroll(), chunk)
            while chunk % unroll:
                unroll -= 1
        if self._chunk_fn is None:
            self._chunk_fn = self._build_chunk_fn()
        tracing = recording()
        chunk_id = self._stats["chunks"]
        t0 = time.perf_counter()
        with annotate("serve.chunk") as span:
            if tracing:
                span.set_metadata(chunk_id=chunk_id, rows=len(rows),
                                  width=width)
            (self._pools, self._fixed, self._cur, key, buf, lens,
             offset) = self._chunk_fn(
                self.params, self._pools, self._fixed, self._cur, key,
                jnp.asarray(gidx), jnp.asarray(sidx), jnp.asarray(kv_start),
                jnp.asarray(budget), width=width, chunk=chunk, unroll=unroll)
        with annotate("serve.chunk.wait") as span:
            # The ONE host transfer of this chunk.
            buf_h, lens_h, offset_h = jax.device_get((buf, lens, offset))  # analysis: allow(TP001)
            # the loop's offset starts at width - chunk, one up per step
            allreduce = (self._step_allreduce[width]
                         * (int(offset_h) - (width - chunk)))
            self._allreduce_bytes += allreduce
            if tracing:
                span.set_metadata(chunk_id=chunk_id,
                                  allreduce_bytes=allreduce)
        self._stats["decode_seconds"] += time.perf_counter() - t0
        self._stats["device_transfers"] += 1
        self._stats["chunks"] += 1
        return key, buf_h, lens_h

    def _emit(self, active: Dict[int, _Request], buf_h, lens_h,
              finished: List[_Request]) -> None:
        """Hand one chunk's tokens to their rows and stream callbacks, then
        evict and finish the rows that ended inside it."""
        from repro.profiling import annotate, recording
        eos = self.cfg.eos_token
        emitted_total = 0
        with annotate("serve.emit") as span:
            now = time.perf_counter()
            for slot in list(active):
                req = active[slot]
                row = self._csched.rows[slot]
                n = int(lens_h[slot])
                emitted = [int(t) for t in buf_h[slot, :n]]
                emitted_total += n
                base = len(req.tokens)
                req.tokens.extend(emitted)
                if emitted and req.t_first is None:
                    req.t_first = now
                if req.stream is not None:
                    for j, t in enumerate(emitted):
                        req.stream(api.StreamEvent(req.rid, t, base + j))
                self._stats["tokens_generated"] += n
                row.length += n
                row.budget_left -= n
                if row.budget_left <= 0 or (eos is not None
                                            and eos in emitted):
                    reason = (api.FINISH_STOP
                              if eos is not None and eos in emitted
                              else api.FINISH_LENGTH)
                    self._csched.evict(row)
                    self._sched.evict(req)
                    del active[slot]
                    self._finish_request(req, reason, now)
                    finished.append(req)
            if recording():
                span.set_metadata(tokens=emitted_total)

    # -- batched generation ---------------------------------------------
    def generate(self, prompts: List[List[int]], max_new_tokens: int,
                 extra_inputs: Optional[Dict[str, jax.Array]] = None
                 ) -> List[List[int]]:
        """Batched generation; prompts beyond ``max_batch`` run in waves."""
        # Validate the whole batch BEFORE the first submit so a bad prompt
        # can't leave earlier requests queued for the next call.
        if not prompts:
            raise ValueError("generate() needs at least one prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if any(not list(p) for p in prompts):
            raise ValueError("empty prompt: each prompt needs >= 1 token")
        for p in prompts:
            if self._scheduler == "continuous":
                if len(list(p)) + max_new_tokens > self._capacity_tokens:
                    raise ValueError(
                        f"prompt ({len(list(p))}) + max_new "
                        f"({max_new_tokens}) exceeds capacity_tokens "
                        f"({self._capacity_tokens})")
            elif len(list(p)) + max_new_tokens > self.cfg.max_len:
                raise ValueError(
                    f"prompt ({len(list(p))}) + max_new ({max_new_tokens}) "
                    f"exceeds max_len ({self.cfg.max_len})")
        if extra_inputs:
            for name, arr in extra_inputs.items():
                if arr.shape[0] != len(prompts):
                    raise ValueError(
                        f"extra input {name!r} leading dim {arr.shape[0]} != "
                        f"len(prompts) {len(prompts)}")
        t0 = time.perf_counter()
        handles = [self.submit(api.Request(prompt=list(p),
                                           max_new_tokens=max_new_tokens,
                                           row=i))
                   for i, p in enumerate(prompts)]
        try:
            self.run(extra_inputs)
        except Exception:
            # drop this call's unserved requests — they must not leak into
            # (and mis-index the extras of) the next call
            rid_set = {h.request_id for h in handles}
            self._queue = [r for r in self._queue if r.rid not in rid_set]
            raise
        self._stats["generate_calls"] += 1
        self._stats["total_seconds"] += time.perf_counter() - t0
        # handles resolved synchronously by the drain above; timeout=0
        # turns a (would-be) bug into a fast failure instead of a hang
        return [h.result(timeout=0).tokens for h in handles]

    # -- one wave: prefill + fused decode + single fetch -----------------
    def _run_wave(self, wave: List[_Request],
                  extra_inputs: Optional[Dict[str, jax.Array]],
                  key: jax.Array) -> None:
        cfg = self.cfg
        b = cfg.max_batch
        # Validate BEFORE admitting: a rejected request must not leak slots.
        need = max(r.max_new for r in wave)    # real token budget (cache need)
        longest = max(len(r.prompt) for r in wave)
        if longest + need > cfg.max_len:       # submit()/_pack_wave guarantee
            raise ValueError(                  # this; keep the guard for raw
                f"prompt ({longest}) + max_new ({need}) exceeds "   # callers
                f"max_len ({cfg.max_len})")
        # The decode width is a pure buffer/loop bound (the fused loop stops
        # at each slot's budget and cache writes stay within plen + need),
        # so it keeps its power-of-two bucket unclamped — one compile per
        # need bucket.  The prompt pad length IS capacity-bound: bucket it,
        # clamped so near-capacity prompts share one clamped bucket instead
        # of falling back to exact per-length sizes (a recompile per
        # distinct prompt length).  The cap prefers the width bucket (fewer
        # distinct plens) and degrades to the exact need only when the
        # bucket would push below the prompt itself.
        width = _bucket_len(need)
        plen = _bucket_len(longest, cfg.max_len - width)
        if plen < longest:
            plen = _bucket_len(longest, cfg.max_len - need)
        if plen < longest:     # unreachable: longest + need <= max_len
            plen = longest
        if extra_inputs and any(r.row is None for r in wave):
            raise ValueError(
                "extra_inputs needs every request submitted with row= "
                "(its index into the extra arrays); generate() does this")
        for r in wave:
            self._sched.admit(r)
        try:
            self._decode_wave(wave, extra_inputs, key, plen, width)
        except Exception as exc:
            for r in wave:
                if r.handle is not None and not r.handle.done:
                    r.handle._set_error(exc)
            raise
        finally:
            # free slots even when prefill/decode throws — one bad request
            # must never brick the pool
            for r in wave:
                self._sched.evict(r)

    def _decode_wave(self, wave: List[_Request],
                     extra_inputs: Optional[Dict[str, jax.Array]],
                     key: jax.Array, plen: int, width: int) -> None:
        cfg = self.cfg
        b = cfg.max_batch
        toks = np.zeros((b, plen), np.int32)
        kv_start = np.full((b,), plen, np.int32)   # empty slots: fully padded
        budget = np.zeros((b,), np.int32)
        for r in wave:
            toks[r.slot, plen - len(r.prompt):] = r.prompt
            kv_start[r.slot] = plen - len(r.prompt)
            budget[r.slot] = r.max_new

        batch = {"tokens": jnp.asarray(toks),
                 "kv_start": jnp.asarray(kv_start)}
        if extra_inputs:
            rows = [r.row for r in wave]
            slots = [r.slot for r in wave]
            for name, arr in extra_inputs.items():
                padded = jnp.zeros((b,) + arr.shape[1:], arr.dtype)
                batch[name] = padded.at[jnp.asarray(slots)].set(
                    jnp.asarray(arr)[jnp.asarray(rows)])
        # Split the wave over the data axes (identity without a mesh).
        batch = self._place_batch(batch)
        # Loop CONTROL state (per-slot budgets/offsets and everything
        # derived from them: done flags, emitted-token buffer) stays
        # replicated: these are a handful of ints per slot, and sharding
        # them turns every ``done.all()`` / budget check inside the fused
        # loop into a blocking cross-device reduction.  Replicated, the
        # whole control path is local to each device; only the model step
        # itself (cache, activations) runs sharded.
        kv_start_d, budget_d = jnp.asarray(kv_start), jnp.asarray(budget)

        cache = self._ensure_cache()
        self._record_prefill_flash_tiles(plen)
        self._plen_buckets.add(int(plen))
        from repro.profiling import annotate
        t0 = time.perf_counter()
        with annotate("serve.prefill_wave"):
            logits0, cache = self._prefill(self.params, batch, cache)
            if cfg.profile:
                # deliberate sync: profile mode wants the true prefill /
                # decode wall-time split, not dispatch-pipeline overlap
                jax.block_until_ready(logits0)   # analysis: allow(TP001)
        t1 = time.perf_counter()

        if self._loop is None:
            self._loop = self._build_loop()
        unroll = min(self._resolve_unroll(), width)
        with annotate("serve.decode_wave"):
            buf, lens, cache = self._loop(
                self.params, cache, logits0, key, kv_start_d,
                budget_d, jnp.int32(plen), width=width, unroll=unroll)
            self._cache = cache

            # The ONE host transfer of this wave (== of the whole generate
            # call when the batch fits the slot pool).
            buf_h, lens_h = jax.device_get((buf, lens))  # analysis: allow(TP001)
        t2 = time.perf_counter()
        self._stats["device_transfers"] += 1
        self._stats["waves"] += 1
        self._stats["prefill_seconds"] += t1 - t0
        self._stats["decode_seconds"] += t2 - t1

        eos = cfg.eos_token
        now = time.perf_counter()
        for r in wave:
            n = int(lens_h[r.slot])
            r.tokens = [int(t) for t in buf_h[r.slot, :n]]
            self._stats["tokens_generated"] += n
            # Wave scheduling streams at wave granularity: every token
            # becomes host-visible at the wave's single transfer, so the
            # callback fires for all of them here (the continuous path
            # streams at chunk granularity instead).
            r.t_first = now if n else None
            if r.stream is not None:
                for j, t in enumerate(r.tokens):
                    r.stream(api.StreamEvent(r.rid, t, j))
            reason = (api.FINISH_STOP if eos is not None and eos in r.tokens
                      else api.FINISH_LENGTH)
            self._finish_request(r, reason, now)

    # -- prefix-cache control --------------------------------------------
    def clear_prefix_cache(self) -> None:
        """Release every cache-pinned page (cold-cache reset).  Live rows
        keep their refs; parity tests and benchmarks use this to compare
        warm vs cold runs on one engine."""
        if self._prefix is not None:
            self._prefix.clear()

    # -- telemetry -------------------------------------------------------
    def stats(self) -> Dict[str, object]:
        """Counters + tuned-block lookup provenance, as one plain dict.

        The key set is VERSIONED and frozen per
        :mod:`repro.serve.stats_schema` (``schema_version`` carries the
        version; the ST001 analysis check and
        :func:`repro.serve.stats_schema.validate_stats` both gate drift).

        Beyond the raw counters (requests, tokens, waves, timings), the
        tuning-framework telemetry:

        * ``hardware`` / ``hardware_platform`` — the resolved hardware
          profile every tile lookup below was keyed by (provenance for
          bench artifacts and the CI backend matrix);
        * ``mesh`` / ``sharding`` — the device topology (axis name → size)
          and, on a mesh, the active sharding rules plus a histogram of the
          param partition specs they produced (``sharding`` is ``None``
          single-device);
        * ``decode_tile_lookups`` — each decode-step GEMM shape mapped to
          its resolved tile and provenance tier
          (``exact``/``nearest``/``generic``/``default``/``fallback``);
        * ``prefill_flash_lookups`` — for ``attention_impl="flash"`` models,
          each prefill bucket's ``(sq, skv, head_dim)`` mapped to its tuned
          ``(bq, bk)`` blocks and provenance;
        * ``registry_hit_stats`` — global per-tier lookup counts.

        Example::

            eng = Engine(model, params, ServeConfig(max_batch=4))
            eng.generate([[1, 2, 3]], max_new_tokens=8)
            eng.stats()["prefill_flash_lookups"]
            # {'8x8x64': {'source': 'nearest', 'tile': '128x128', ...}}
        """
        from repro.core.registry import GLOBAL_REGISTRY
        from repro.launch.mesh import describe_mesh
        from repro.serve.prefix_cache import PrefixCache
        out = dict(self._stats)
        out["schema_version"] = SCHEMA_VERSION
        out["hardware"] = self.hardware
        out["hardware_platform"] = self._platform
        out["mesh"] = describe_mesh(self.mesh)
        if self.mesh is None:
            out["sharding"] = None
        else:
            from repro.distributed import sharding as sh
            out["sharding"] = {
                "rules": {
                    "tensor_axis": self.rules.tensor_axis,
                    "fsdp_axis": self.rules.fsdp_axis,
                    "batch_axes": list(self.rules.batch_axes),
                    "sequence_axis": self.rules.sequence_axis,
                },
                "params": sh.sharding_summary(self.mesh, self.rules,
                                              self.model.template),
            }
        out["prefill_plen_buckets"] = sorted(self._plen_buckets)
        out["decode_unroll"] = self._unroll
        out["decode_unroll_source"] = self._unroll_source
        out["scheduler"] = self._scheduler
        out["scheduler_forced"] = self._scheduler_forced
        if self._scheduler == "continuous":
            out["decode_chunk"] = self._chunk
            out["capacity_tokens"] = self._capacity_tokens
            out["page_size"] = self._page_size
            out["page_size_source"] = self._page_size_source
            out["pages"] = None
            if self._alloc is not None:
                out["pages"] = {
                    "page_size": self._alloc.page_size,
                    "usable_pages": self._alloc.usable_pages,
                    "used_pages": self._alloc.used_pages,
                    "free_pages": self._alloc.free_pages,
                    "utilization": self._alloc.utilization(),
                    "high_water_pages": self._alloc.high_water_pages,
                    "alloc_count": self._alloc.alloc_count,
                    "free_count": self._alloc.free_count,
                }
            out["admissions"] = (self._csched.admissions
                                 if self._csched is not None else 0)
            out["evictions"] = (self._csched.evictions
                                if self._csched is not None else 0)
            out["preemptions"] = (self._csched.preemptions
                                  if self._csched is not None else 0)
            out["allreduce_bytes"] = self._allreduce_bytes
        out["prefix_cache"] = (self._prefix.stats()
                               if self._prefix is not None
                               else PrefixCache.disabled_stats())
        out["latency"] = {
            "count": len(self._lat_tok),
            "ttft_s": _percentiles(self._lat_ttft),
            "tok_per_s": _percentiles(self._lat_tok),
        }
        out["slots"] = self.cfg.max_batch
        out["slots_admitted"] = self._sched.admitted
        out["slots_evicted"] = self._sched.evicted
        out["slot_reuses"] = self._sched.reuses
        out["decode_tile_lookups"] = self._tile_lookups
        out["prefill_flash_lookups"] = dict(self._prefill_flash_lookups)
        out["registry_hit_stats"] = dict(GLOBAL_REGISTRY.hit_stats)
        return out
