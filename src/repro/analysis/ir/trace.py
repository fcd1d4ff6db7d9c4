"""Dry-trace one :class:`~repro.analysis.ir.matrix.IRCase` and distill the
lowered program into a check-ready :class:`EntrySummary`.

The tracer builds a *real* engine (tiny ``.reduced()`` params, so a CPU
host pays seconds, not minutes), then lowers each jitted entry point with
``jitted.lower(...)`` / ``jitted.trace(...)`` — tracing and XLA compilation
only, **no device execution**.  Compiling matters: SPMD partitioning (and
therefore every collective the program will issue) only exists in
``lowered.compile().as_text()``, not in the pre-partitioning StableHLO, so
a collective-placement check that skipped compile would be checking air.

Everything the check families need is extracted *here*, at trace time,
into a JSON-serializable summary: jaxpr hash + primitive histogram
(IR005), dtype converts and dot accumulate dtypes (IR002), buffer
assignment numbers (IR003), and the collectives reachable from while-loop
bodies (IR001, reusing :mod:`repro.launch.hlo_stats`'s HLO parser).  The
summary — never the multi-MB HLO text — is what lands in the ``.ir_cache/``
disk cache, keyed on (source tree digest, jax version, case id), so checks
re-run instantly while nothing changed.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
import time
from typing import Any, Dict, List, Optional, Tuple

from repro.analysis.ir.matrix import SERVE_KW, IRCase
from repro.launch.hlo_stats import (COLLECTIVE_OPS, _parse_computations,
                                    _shape_numel_bytes)

#: bump when the summary extraction changes shape — invalidates .ir_cache
SUMMARY_SCHEMA_VERSION = 3

#: params leaves at least this many elements wide (and >= 2-d) count as
#: "weights" for the weight-sized-collective and weight-upcast checks
WEIGHT_NUMEL_MIN = 1024

# pointer reprs (bound methods, closures) that leak into jaxpr pretty-prints
_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")


@dataclasses.dataclass
class EntrySummary:
    """Check-ready distillation of one lowered entry point."""
    entry: str
    jaxpr_hash: str
    prim_histogram: Dict[str, int]
    # convert_element_type sites: {"src", "dst", "numel", "dims"}
    converts: List[dict]
    # dot_general sites: {"lhs", "rhs", "out"}
    dots: List[dict]
    f64_avals: int
    # compiled buffer assignment: argument/output/temp/peak bytes (None
    # where the backend does not report a field — CPU omits peak)
    memory: Dict[str, Optional[int]]
    # collectives reachable from while-loop bodies:
    # {"op", "numel", "bytes", "dims"}
    while_collectives: List[dict]
    # all collectives in the compiled module (same record shape)
    collectives: List[dict]
    seconds: float = 0.0

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, blob: dict) -> "EntrySummary":
        return cls(**blob)


@dataclasses.dataclass
class CaseResult:
    """All entry summaries of one case plus the case-level context the
    checks key on (weight sizes, resolved hardware, failures)."""
    case_id: str
    entries: Dict[str, EntrySummary]
    # >=2-d, >=WEIGHT_NUMEL_MIN-element params leaf shapes, plus their
    # leading-dim-sliced variants (what a layer scan's body sees of a
    # stacked (L, ...) leaf) — the identity "weight-sized" checks match on
    weight_shapes: List[List[int]]
    params_bytes: int
    hardware: str
    jax_version: str
    # entry -> "ExcType: message" for entries that failed to trace/compile
    errors: Dict[str, str] = dataclasses.field(default_factory=dict)
    cached: bool = False
    seconds: float = 0.0

    def to_json(self) -> dict:
        blob = dataclasses.asdict(self)
        blob["schema_version"] = SUMMARY_SCHEMA_VERSION
        return blob

    @classmethod
    def from_json(cls, blob: dict) -> "CaseResult":
        blob = dict(blob)
        blob.pop("schema_version", None)
        blob["entries"] = {k: EntrySummary.from_json(v)
                           for k, v in blob["entries"].items()}
        return cls(**blob)


# ---------------------------------------------------------------------------
# jaxpr distillation
# ---------------------------------------------------------------------------

def _sub_jaxprs(value):
    """Yield every Jaxpr nested in an eqn param value (ClosedJaxpr, bare
    Jaxpr, or tuples of either — cond branches)."""
    vals = value if isinstance(value, (tuple, list)) else (value,)
    for v in vals:
        inner = getattr(v, "jaxpr", v)
        if hasattr(inner, "eqns"):
            yield inner


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _iter_eqns(sub)


def _numel(aval) -> int:
    n = 1
    for d in getattr(aval, "shape", ()):
        n *= int(d)
    return n


def canonical_jaxpr_text(jaxpr) -> str:
    """Pretty-printed jaxpr with process-specific noise (object addresses in
    embedded callable reprs) scrubbed, so the hash is stable across
    processes on one jax version."""
    return _ADDR_RE.sub("0x?", str(jaxpr))


def summarize_jaxpr(closed_jaxpr) -> Tuple[str, Dict[str, int], List[dict],
                                           List[dict], int]:
    """-> (hash, prim histogram, converts, dots, f64 aval count)."""
    text = canonical_jaxpr_text(closed_jaxpr)
    digest = hashlib.sha256(text.encode()).hexdigest()
    hist: Dict[str, int] = {}
    converts: List[dict] = []
    dots: List[dict] = []
    f64 = 0
    root = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    for eqn in _iter_eqns(root):
        name = eqn.primitive.name
        hist[name] = hist.get(name, 0) + 1
        out_aval = eqn.outvars[0].aval if eqn.outvars else None
        for ov in eqn.outvars:
            if str(getattr(ov.aval, "dtype", "")) == "float64":
                f64 += 1
        if name == "convert_element_type" and out_aval is not None:
            src = str(eqn.invars[0].aval.dtype)
            dst = str(out_aval.dtype)
            if src != dst:
                converts.append({"src": src, "dst": dst,
                                 "numel": _numel(out_aval),
                                 "dims": [int(d) for d in out_aval.shape]})
        elif name == "dot_general" and out_aval is not None:
            dots.append({"lhs": str(eqn.invars[0].aval.dtype),
                         "rhs": str(eqn.invars[1].aval.dtype),
                         "out": str(out_aval.dtype)})
    return digest, hist, converts, dots, f64


# ---------------------------------------------------------------------------
# compiled-HLO distillation
# ---------------------------------------------------------------------------

_SHAPE_DIMS_RE = re.compile(r"[a-z][a-z0-9]*\[([\d,]*)\]")


def _collective_record(op: str, instr) -> dict:
    base = op.replace("-start", "")
    numel, nbytes = _shape_numel_bytes(instr.type_tok)
    if op.endswith("-start") and base in ("all-gather", "all-reduce"):
        numel //= 2      # -start returns an (operand, result) tuple
        nbytes //= 2
    # result dims: the last shape token (for -start tuples the second
    # element is the gathered result; plain ops have one token)
    toks = _SHAPE_DIMS_RE.findall(instr.type_tok)
    dims = [int(d) for d in toks[-1].split(",") if d] if toks else []
    return {"op": base, "numel": numel, "bytes": nbytes, "dims": dims}


def hlo_collectives(text: str) -> Tuple[List[dict], List[dict]]:
    """-> (all collectives, collectives reachable from while bodies).

    Reachability follows ``calls=`` / ``body=`` / ``condition=`` edges from
    every while instruction's body, so a collective hidden two fusions deep
    inside the fused decode loop still counts as "inside the loop".
    """
    comps = _parse_computations(text)
    edge_re = re.compile(r"(?:calls|body|condition|branch_computations)="
                         r"\{?%?([\w.\-, %]+)\}?")
    body_re = re.compile(r"body=%?([\w.\-]+)")

    edges: Dict[str, List[str]] = {}
    roots: List[str] = []
    for cname, comp in comps.items():
        outs: List[str] = []
        for ins in comp.instrs:
            for m in edge_re.finditer(ins.line):
                for tgt in m.group(1).split(","):
                    tgt = tgt.strip().lstrip("%")
                    if tgt in comps:
                        outs.append(tgt)
            if ins.op == "while":
                bm = body_re.search(ins.line)
                if bm and bm.group(1) in comps:
                    roots.append(bm.group(1))
        edges[cname] = outs

    in_while: set = set()
    stack = list(roots)
    while stack:
        name = stack.pop()
        if name in in_while:
            continue
        in_while.add(name)
        stack.extend(edges.get(name, ()))

    every: List[dict] = []
    while_body: List[dict] = []
    for cname, comp in comps.items():
        for ins in comp.instrs:
            base = ins.op.replace("-start", "")
            if base in COLLECTIVE_OPS and not ins.op.endswith("-done"):
                rec = _collective_record(ins.op, ins)
                every.append(rec)
                if cname in in_while:
                    while_body.append(rec)
    return every, while_body


def _memory_record(compiled) -> Dict[str, Optional[int]]:
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return {"argument_bytes": None, "output_bytes": None,
                "temp_bytes": None, "peak_bytes": None}
    def _get(attr):
        v = getattr(mem, attr, None)
        return int(v) if v is not None else None
    return {"argument_bytes": _get("argument_size_in_bytes"),
            "output_bytes": _get("output_size_in_bytes"),
            "temp_bytes": _get("temp_size_in_bytes"),
            "peak_bytes": _get("peak_memory_in_bytes")}


def summarize_entry(entry: str, jitted, *args, **static) -> EntrySummary:
    """Lower + trace + compile one jitted entry point (never execute it)."""
    t0 = time.time()
    traced = jitted.trace(*args, **static)
    digest, hist, converts, dots, f64 = summarize_jaxpr(traced.jaxpr)
    compiled = jitted.lower(*args, **static).compile()
    collectives, while_collectives = hlo_collectives(compiled.as_text())
    return EntrySummary(
        entry=entry, jaxpr_hash=digest, prim_histogram=hist,
        converts=converts, dots=dots, f64_avals=f64,
        memory=_memory_record(compiled),
        while_collectives=while_collectives, collectives=collectives,
        seconds=round(time.time() - t0, 2))


# ---------------------------------------------------------------------------
# case tracing
# ---------------------------------------------------------------------------

def _weight_shapes(params) -> List[List[int]]:
    """Exact shapes that identify "a weight" in the traced programs: every
    >=2-d, >=WEIGHT_NUMEL_MIN-element params leaf, plus the leading-dim
    slice of stacked (L, ...) leaves — what a layer scan's body sees.
    Matching on full shape (not numel) keeps activations whose element
    count happens to collide with a weight's out of IR001/IR002."""
    import jax
    out = set()
    for leaf in jax.tree_util.tree_leaves(params):
        if getattr(leaf, "ndim", 0) >= 2 and leaf.size >= WEIGHT_NUMEL_MIN:
            shape = tuple(int(d) for d in leaf.shape)
            out.add(shape)
            if len(shape) >= 3:
                sliced = shape[1:]
                n = 1
                for d in sliced:
                    n *= d
                if n >= WEIGHT_NUMEL_MIN:
                    out.add(sliced)
    return sorted(list(s) for s in out)


def _params_bytes(params) -> int:
    import jax
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(params))


def _extras(model, b):
    """Zero-filled extra model inputs (image tiles, audio features) shaped
    like the engine pads them — so VLM/audio towers are part of the trace."""
    import jax.numpy as jnp
    return {name: jnp.zeros(sds.shape, sds.dtype)
            for name, sds in model.extra_inputs(b).items()}


def _trace_wave_entries(eng, model, case: IRCase, plen: int,
                        out: Dict[str, EntrySummary],
                        errors: Dict[str, str]) -> None:
    import jax
    import jax.numpy as jnp
    b = eng.cfg.max_batch
    batch = {"tokens": jnp.zeros((b, plen), jnp.int32),
             "kv_start": jnp.zeros((b,), jnp.int32), **_extras(model, b)}
    batch = eng._place_batch(batch)
    cache = eng._ensure_cache()
    try:
        out["prefill"] = summarize_entry(
            "prefill", eng._prefill, eng.params, batch, cache)
    except Exception as e:
        errors["prefill"] = f"{type(e).__name__}: {e}"
    try:
        logits_aval = jax.eval_shape(eng._prefill, eng.params, batch, cache)[0]
        loop = eng._loop or eng._build_loop()
        eng._loop = loop
        width = 8
        unroll = min(eng._resolve_unroll(), width)
        out["decode_loop"] = summarize_entry(
            "decode_loop", loop, eng.params, cache,
            jnp.zeros(logits_aval.shape, logits_aval.dtype),
            jax.random.PRNGKey(0), jnp.zeros((b,), jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.int32(plen),
            width=width, unroll=unroll)
    except Exception as e:
        errors["decode_loop"] = f"{type(e).__name__}: {e}"


def _trace_train_entry(model, case: IRCase, mesh,
                       out: Dict[str, EntrySummary],
                       errors: Dict[str, str]) -> None:
    """Train-step lowering, abstract end to end (the dryrun.py pattern):
    ShapeDtypeStruct state/batch, explicit shardings on a mesh."""
    import jax
    from repro.configs.base import ShapeSpec
    from repro.distributed import sharding as sh
    from repro.launch import specs as specs_mod
    from repro.optim.adamw import AdamW
    from repro.train import trainer as tr
    try:
        shape = ShapeSpec("ir_train", 32, 8 if mesh is not None else 4,
                          "train")
        batch = specs_mod.train_batch_specs(model, shape)
        optimizer = AdamW(learning_rate=1e-4)
        state_abs = tr.abstract_train_state(model, optimizer)
        step = tr.make_train_step(model, optimizer)
        if mesh is not None:
            rules = sh.rules_for_mesh(mesh)      # FSDP: the training rules
            from repro.distributed.ctx import activation_policy
            with mesh, activation_policy(mesh, rules):
                jitted = jax.jit(
                    step,
                    in_shardings=(tr.state_shardings(mesh, rules, model),
                                  sh.batch_shardings(mesh, rules, batch)),
                    out_shardings=(tr.state_shardings(mesh, rules, model),
                                   None),
                    donate_argnums=(0,))
                out["train_step"] = summarize_entry(
                    "train_step", jitted, state_abs, batch)
        else:
            jitted = jax.jit(step, donate_argnums=(0,))
            out["train_step"] = summarize_entry(
                "train_step", jitted, state_abs, batch)
    except Exception as e:
        errors["train_step"] = f"{type(e).__name__}: {e}"


def _trace_continuous_entries(eng, model, case: IRCase, plen: int,
                              out: Dict[str, EntrySummary],
                              errors: Dict[str, str]) -> None:
    import jax
    import jax.numpy as jnp
    b = eng.cfg.max_batch
    g = eng._admit_rows             # rows per admission prefill call
    eng._ensure_pool()
    key = jax.random.PRNGKey(0)
    try:
        batch = {"tokens": jnp.zeros((g, plen), jnp.int32),
                 "kv_start": jnp.zeros((g,), jnp.int32), **_extras(model, g)}
        batch = eng._place_batch(batch)
        scratch = eng._scratch_cache(plen)
        admit = eng._admit_fn or eng._build_admit_fn()
        eng._admit_fn = admit
        out["admit"] = summarize_entry(
            "admit", admit, eng.params, batch, scratch, eng._pools,
            eng._fixed, eng._cur, key, jnp.zeros((g, plen), jnp.int32),
            jnp.zeros((g,), jnp.int32))
    except Exception as e:
        errors["admit"] = f"{type(e).__name__}: {e}"
    try:
        chunk = eng._chunk
        width = 16
        unroll = min(eng._resolve_unroll(), chunk)
        while chunk % unroll:
            unroll -= 1
        chunk_fn = eng._chunk_fn or eng._build_chunk_fn()
        eng._chunk_fn = chunk_fn
        out["decode_chunk"] = summarize_entry(
            "decode_chunk", chunk_fn, eng.params, eng._pools, eng._fixed,
            eng._cur, key, jnp.zeros((b, width), jnp.int32),
            jnp.zeros((b, chunk), jnp.int32), jnp.zeros((b,), jnp.int32),
            jnp.zeros((b,), jnp.int32), width=width, chunk=chunk,
            unroll=unroll)
    except Exception as e:
        errors["decode_chunk"] = f"{type(e).__name__}: {e}"


def trace_case(case: IRCase, rules_override=None) -> CaseResult:
    """Dry-trace every entry point of one case.

    ``rules_override`` installs explicit ambient sharding rules (via
    ``distributed.ctx.use_mesh``) instead of the engine's own inference-TP
    default — how the seeded-regression test re-creates the PR 6 bug
    (``fsdp=True`` rules putting weight all-gathers inside the decode loop)
    without editing engine code.
    """
    import contextlib
    import dataclasses as _dc

    import jax

    from repro.configs.catalog import ARCHITECTURES
    from repro.distributed import ctx as dctx
    from repro.launch.mesh import build_mesh
    from repro.models import build_model
    from repro.serve.engine import Engine, ServeConfig

    t0 = time.time()
    cfg = ARCHITECTURES[case.family].reduced()
    cfg = _dc.replace(cfg, dtype=case.dtype)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    mesh = build_mesh(case.mesh_spec)
    scope = (dctx.use_mesh(mesh, rules_override)
             if rules_override is not None and mesh is not None
             else contextlib.nullcontext())
    with scope:
        eng = Engine(model, params, ServeConfig(
            scheduler=case.scheduler,
            mesh=None if rules_override is not None else case.mesh_spec,
            **SERVE_KW))

    plen = 16
    out: Dict[str, EntrySummary] = {}
    errors: Dict[str, str] = {}
    if case.scheduler == "wave":
        _trace_wave_entries(eng, model, case, plen, out, errors)
        _trace_train_entry(model, case, mesh, out, errors)
    else:
        if eng._scheduler != "continuous":
            errors["admit"] = (f"RuntimeError: engine forced scheduler "
                               f"{eng._scheduler!r} ({eng._scheduler_forced})")
        else:
            _trace_continuous_entries(eng, model, case, plen, out, errors)

    return CaseResult(
        case_id=case.case_id, entries=out,
        weight_shapes=_weight_shapes(eng.params),
        params_bytes=_params_bytes(eng.params),
        hardware=eng.hardware, jax_version=jax.__version__,
        errors=errors, seconds=round(time.time() - t0, 2))


# ---------------------------------------------------------------------------
# disk cache
# ---------------------------------------------------------------------------

def repo_root() -> str:
    here = os.path.abspath(os.path.dirname(__file__))   # src/repro/analysis/ir
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(here))))


def source_digest(root: Optional[str] = None) -> str:
    """Digest of every ``src/repro/**/*.py`` — the cache invalidation key.
    Any source edit retraces everything; a docs/CI edit retraces nothing."""
    root = root or repo_root()
    src = os.path.join(root, "src", "repro")
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in sorted(os.walk(src)):
        dirnames.sort()
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            h.update(os.path.relpath(path, src).encode())
            with open(path, "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def default_cache_dir() -> str:
    return os.path.join(repo_root(), ".ir_cache")


def cache_key(case: IRCase, src_digest: str) -> str:
    import jax
    raw = (f"v{SUMMARY_SCHEMA_VERSION}:{src_digest}:{jax.__version__}:"
           f"{case.case_id}:{sorted(SERVE_KW.items())}")
    return hashlib.sha256(raw.encode()).hexdigest()[:32]


def traced_case_cached(case: IRCase, *, cache_dir: Optional[str] = None,
                       src_digest: Optional[str] = None,
                       use_cache: bool = True) -> CaseResult:
    """`trace_case` behind the ``.ir_cache/`` summary cache."""
    cache_dir = cache_dir or default_cache_dir()
    src_digest = src_digest or source_digest()
    path = os.path.join(cache_dir, f"{cache_key(case, src_digest)}.json")
    if use_cache and os.path.exists(path):
        try:
            with open(path) as f:
                result = CaseResult.from_json(json.load(f))
            result.cached = True
            return result
        except Exception:
            pass                          # corrupt entry: retrace
    result = trace_case(case)
    if use_cache:
        os.makedirs(cache_dir, exist_ok=True)
        with open(path, "w") as f:
            json.dump(result.to_json(), f, indent=1, sort_keys=True)
    return result
