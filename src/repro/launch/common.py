"""Shared CLI surface of the launch drivers.

Every driver used to re-declare ``--hardware``/``--mesh``/``--tuned-dir``/
``--trace-dir`` by hand, and the copies drifted (names, defaults, help
text).  This module is the single declaration:

* :func:`add_common_args` — the tuning/topology flags every driver takes,
  with identical names and help everywhere;
* :func:`add_serving_args` — the serving-engine group (scheduler, paged-KV
  sizing, prefix cache) shared by ``serve.py`` and the benchmarks;
* :func:`deprecated_flag` — registers a retired flag that still parses:
  using it warns once and forwards its value onto the replacement, so old
  command lines keep working one release while printing their migration.
* :func:`enable_compile_cache` — JAX's persistent compilation cache, which
  every launcher turns on first thing in ``main()``;
* :func:`apply_latency_hiding_flags` — the hardware profile's
  collective-overlap compiler flags, set before a mesh run's backend starts.

Drivers call these, then add their driver-specific flags on top.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import warnings
from typing import List, Optional

#: the cache's fixed in-repo home when the caller places none (listed in
#: .gitignore).  A fixed path, because the path is part of the cache key.
DEFAULT_COMPILE_CACHE = (pathlib.Path(__file__).resolve().parents[3]
                         / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it itself, and no other
    directory is set in code.  Otherwise the cache lives at
    :data:`DEFAULT_COMPILE_CACHE`.  Call it at the start of a ``main()``,
    never at import (tests leave the cache off).
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)


def apply_latency_hiding_flags(hardware: Optional[str]) -> List[str]:
    """Set the named profile's latency-hiding compiler flags; returns the
    flags added.

    The runtime reads its flags once, when the backend starts, so a
    launcher calls this before its first device touch, and only with a
    profile it was named (``--hardware`` or ``$REPRO_HARDWARE``): detecting
    the profile would start the backend first.  TPU flags go to
    ``LIBTPU_INIT_ARGS``, where libtpu reads them (``XLA_FLAGS`` refuses
    ``xla_tpu_*`` flags as unknown and aborts); other backends' go to
    ``XLA_FLAGS``.  A flag already set is left as the caller set it.
    """
    from repro.core.hardware import (HARDWARE_ENV, PLATFORM_TPU,
                                     canonical_name, find_profile)
    name = hardware or os.environ.get(HARDWARE_ENV)
    prof = find_profile(canonical_name(name)) if name else None
    if prof is None:
        return []
    env = ("LIBTPU_INIT_ARGS" if prof.platform == PLATFORM_TPU
           else "XLA_FLAGS")
    current = os.environ.get(env, "")
    added = [f for f in prof.xla_latency_flags
             if f.split("=")[0] not in current]
    if added:
        os.environ[env] = " ".join(filter(None, [current] + added))
    return added


def add_common_args(ap: argparse.ArgumentParser) -> None:
    """The flags every launch driver shares, declared once."""
    ap.add_argument("--hardware", default=None,
                    help="hardware profile the engine tunes against "
                         "(default: $REPRO_HARDWARE or auto-detect)")
    ap.add_argument("--mesh", default=None,
                    help="device mesh spec: 'data=N,model=M' or 'auto' "
                         "(default: single-device)")
    ap.add_argument("--stats", action="store_true",
                    help="print engine/trainer stats (throughput, tile "
                         "provenance)")
    ap.add_argument("--tuned-dir", default=None,
                    help="tuning-DB dir (default: $REPRO_TUNED_DIR or "
                         "repo tuned/)")
    ap.add_argument("--trace-dir", default=None,
                    help="capture a jax.profiler trace of the run into "
                         "this dir (post-process: scripts/profile.py)")


def add_serving_args(ap: argparse.ArgumentParser) -> None:
    """The serving-engine knob group (ServeConfig surface)."""
    grp = ap.add_argument_group(
        "serving", "continuous-batching engine configuration")
    grp.add_argument("--scheduler", choices=["continuous", "wave"],
                     default="continuous",
                     help="continuous = paged KV + admit/evict at chunk "
                          "boundaries (default); wave = slot-per-request")
    grp.add_argument("--page-size", type=int, default=None,
                     help="paged-KV page size in tokens (default: tuned "
                          "paged_attn entry for this hardware/mesh)")
    grp.add_argument("--capacity-tokens", type=int, default=None,
                     help="paged-pool capacity in tokens (default: "
                          "max_batch * max_len)")
    grp.add_argument("--decode-chunk", type=int, default=8,
                     help="tokens per fused chunk between scheduling "
                          "boundaries (power of two)")
    grp.add_argument("--no-prefix-cache", action="store_true",
                     help="disable shared-prefix KV reuse (continuous "
                          "scheduler only; on by default)")


class _DeprecatedAction(argparse.Action):
    """Store the value, remember it was used, and warn at parse time."""

    def __call__(self, parser, namespace, values, option_string=None):
        warnings.warn(
            f"{option_string} is deprecated; use {self.const} instead "
            f"(value forwarded)", DeprecationWarning, stacklevel=2)
        print(f"[deprecated] {option_string} -> {self.const}")
        setattr(namespace, self.dest, values)
        used = getattr(namespace, "_deprecated_used", set())
        used.add(self.dest)
        setattr(namespace, "_deprecated_used", used)


def deprecated_flag(ap: argparse.ArgumentParser, old: str, new: str,
                    **kwargs) -> None:
    """Register retired flag ``old`` as a warn-and-forward alias.

    The parsed value lands on ``old``'s own dest;
    :func:`forward_deprecated` moves it onto ``new``'s dest afterwards
    (only when the modern flag was not given — the modern flag wins).
    """
    kwargs.setdefault("default", None)
    kwargs.setdefault("help", argparse.SUPPRESS)
    ap.add_argument(old, action=_DeprecatedAction, const=new, **kwargs)


def forward_deprecated(args: argparse.Namespace, mapping) -> None:
    """Resolve warn-and-forward aliases after parsing.

    ``mapping`` is ``{old_dest: (new_dest, convert)}``; each used alias
    whose modern dest is still at its default (None/falsy) gets the
    converted legacy value.
    """
    used = getattr(args, "_deprecated_used", set())
    for old_dest, (new_dest, convert) in mapping.items():
        if old_dest not in used:
            continue
        if getattr(args, new_dest, None):
            continue                      # the modern flag wins
        setattr(args, new_dest, convert(getattr(args, old_dest)))
