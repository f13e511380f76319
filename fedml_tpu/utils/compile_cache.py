"""Where JAX's persistent compilation cache lives — decided from outside.

The cache directory is part of the cache key, so it must not move between
runs.  ``JAX_COMPILATION_CACHE_DIR`` (read by JAX itself) wins: where it is
set, nothing here touches the setting.  Where it is not, the cache sits at
the fixed ``<checkout>/.jax_cache`` (git-ignored).  The Parrot executable
cache takes its directory from the same setting
(``ParrotAPI._aot_cache_path``).

`configure_compile_cache` also starts the count of what building programs
costs the process: ``fedml_program_build_seconds_total{stage}`` and
``fedml_programs_built_total{source}``, fed by JAX's own monitoring events.
"""

from __future__ import annotations

import os
import threading

from ..core.mlops import metrics as _metrics

#: the checkout that holds this package
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Point JAX at the persistent compilation cache and return its
    directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    _count_program_builds()
    return path


#: JAX's duration events (0.9.0: ``jax/_src/dispatch.py``,
#: ``compiler.py``) by the ``stage`` their seconds are filed under
_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_fetch",
}
#: JAX's plain events (``compiler.py``, ``compilation_cache.py``) that say
#: where the program under way came from.  A miss is recorded when a
#: freshly compiled program is written to the persistent cache: a program
#: that a warm run would have fetched
_SOURCE_OF = {
    "/jax/compilation_cache/cache_hits": "cache",
    "/jax/compilation_cache/cache_misses": "compiled",
}

_listening = False
#: the thread's program under way, between its cache event and the end of
#: its backend-compile event, which JAX records around the cache's lookup
_under_way = threading.local()


def _on_event(event: str, **_kw) -> None:
    source = _SOURCE_OF.get(event)
    if source is not None:
        _under_way.source = source


def _on_duration(event: str, secs: float, **_kw) -> None:
    stage = _STAGE_OF.get(event)
    if stage is None:
        return
    if stage == "cache_fetch":
        _under_way.fetch_s = secs
    elif stage == "backend":
        # a program too quick to compile to be stored
        # (``jax_persistent_cache_min_compile_time_secs``) records neither
        # a hit nor a miss: compiled anew in every run
        source = getattr(_under_way, "source", None) or "small"
        _metrics.counter(
            "fedml_programs_built_total",
            "Programs built, by source: fetched from the persistent "
            "cache, compiled and stored in it, or too small to store",
            labels=("source",)).labels(source=source).inc()
        # the event spans the lookup: what the fetch took is filed once
        secs = max(secs - getattr(_under_way, "fetch_s", 0.0), 0.0)
        _under_way.source = None
        _under_way.fetch_s = 0.0
    _metrics.counter(
        "fedml_program_build_seconds_total",
        "Seconds spent building programs, by stage: trace, lower, "
        "backend (compile) and cache_fetch",
        labels=("stage",)).labels(stage=stage).inc(secs)


def _count_program_builds() -> None:
    """Register the listeners, once a process (JAX has no public way to
    take one off again)."""
    global _listening
    if _listening:
        return
    _listening = True
    from jax import monitoring

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
