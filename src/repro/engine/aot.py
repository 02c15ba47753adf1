"""Zero-cold-start support: JAX persistent compilation cache + AOT helpers.

The step caches in ``engine/runner.py`` and ``train/trainer.py`` make
compiles-per-*process* the invariant (one per geometry).  This module
extends that to compiles-per-*cluster*:

  * ``enable_persistent_cache()`` turns on JAX's persistent compilation
    cache (thresholds zeroed so every executable persists, including the
    small CPU-backend steps this repro's tests run).  Any later ``jit`` —
    or AOT ``lower().compile()`` — that re-derives an already-cached
    computation deserializes the executable instead of invoking XLA.  The
    cache has one home: ``$JAX_COMPILATION_CACHE_DIR`` when that is set
    (jax reads it itself, and nothing here overrides it), else the fixed,
    git-ignored ``<checkout>/.cache/jax``.  A fixed path matters because
    the path is part of what the cache is keyed on: a directory that moves
    never hits.
  * ``xla_cache_counters()`` counts *actual* XLA compiles vs disk
    deserializations via ``jax.monitoring`` events, which is how the
    cross-process tests assert "0 XLA compiles" in a warm process — the
    step caches' own ``compiles`` counters count traces, which still
    happen once per process.
  * ``abstract_like`` / ``compile_bytes_estimate`` back the engines'
    ``warmup()`` APIs: geometry declared up front is lowered from
    ``ShapeDtypeStruct``s and compiled ahead of time, so the first real
    batch runs a ready executable.

``Session(store=...)`` (repro.api) enables the persistent cache by
default, next to its artifact store.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional

import jax

__all__ = [
    "DEFAULT_CACHE_DIR",
    "enable_persistent_cache",
    "persistent_cache_status",
    "xla_cache_counters",
    "abstract_like",
    "compile_bytes_estimate",
]

# monitoring events jax records around every compile request (see
# jax/_src/compiler.py): a "request" consults the cache, then exactly one
# of hit (deserialized from disk) or miss (XLA ran, result persisted).
_EVT_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_EVT_HITS = "/jax/compilation_cache/cache_hits"
_EVT_MISSES = "/jax/compilation_cache/cache_misses"

_COUNTERS: Dict[str, int] = {"requests": 0, "hits": 0, "misses": 0}
_LISTENING = False

# where the cache lives when $JAX_COMPILATION_CACHE_DIR is not set
DEFAULT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".cache", "jax"
))


def _listener(event: str, **kwargs) -> None:
    if event == _EVT_REQUESTS:
        _COUNTERS["requests"] += 1
    elif event == _EVT_HITS:
        _COUNTERS["hits"] += 1
    elif event == _EVT_MISSES:
        _COUNTERS["misses"] += 1


def enable_persistent_cache() -> str:
    """Turn on the JAX persistent compilation cache and start counting
    hit/miss events.  The directory is ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else ``DEFAULT_CACHE_DIR``.  Idempotent; returns the directory."""
    global _LISTENING
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not _LISTENING:
        jax.monitoring.register_event_listener(_listener)
        _LISTENING = True
    return jax.config.jax_compilation_cache_dir


def xla_cache_counters() -> Dict[str, int]:
    """Persistent-cache traffic since ``enable_persistent_cache``:
    ``requests`` (compile requests that consulted the cache), ``hits``
    (deserialized from disk — no XLA invocation), ``misses`` (XLA actually
    compiled).  A warm process shows ``misses == 0, requests > 0``."""
    return dict(_COUNTERS)


def persistent_cache_status() -> Dict[str, Any]:
    """JSON-friendly snapshot for bench artifacts: whether the cache is
    enabled, where, how many executables it holds, and this process's
    hit/miss traffic."""
    d = getattr(jax.config, "jax_compilation_cache_dir", None)
    entries = 0
    nbytes = 0
    if d and os.path.isdir(d):
        for name in os.listdir(d):
            if name.endswith("-cache"):
                entries += 1
                try:
                    nbytes += os.path.getsize(os.path.join(d, name))
                except OSError:
                    pass
    return {
        "enabled": bool(d),
        "dir": d,
        "entries": entries,
        "bytes": nbytes,
        **xla_cache_counters(),
    }


def abstract_like(tree: Any) -> Any:
    """ShapeDtypeStruct skeleton of a pytree — what ``warmup`` lowers from
    so no concrete params/batch need exist.  ShapeDtypeStruct leaves pass
    through, so abstract trees (``jax.eval_shape`` output) are accepted
    unchanged."""
    return jax.tree.map(
        lambda x: x
        if isinstance(x, jax.ShapeDtypeStruct)
        else jax.ShapeDtypeStruct(jax.numpy.shape(x), jax.numpy.result_type(x)),
        tree,
    )


def compile_bytes_estimate(compiled) -> Optional[int]:
    """Rough retained-bytes estimate for an AOT-compiled executable
    (generated code + temp allocations); None when the backend's
    ``memory_analysis`` cannot say."""
    try:
        m = compiled.memory_analysis()
        if m is None:
            return None
        total = 0
        for attr in (
            "generated_code_size_in_bytes",
            "temp_size_in_bytes",
            "output_size_in_bytes",
        ):
            v = getattr(m, attr, None)
            if v is not None:
                total += int(v)
        return total or None
    except Exception:
        return None
