"""Process-level JAX set-up shared by the entry points (CLI, API server,
bench, chip_smoke): where the persistent compilation cache lives.

Platform selection is JAX's own: ``JAX_PLATFORMS=cpu`` in the environment
holds a process to the CPU (the tests); unset, JAX takes the attached
accelerator and fails at start-up if it cannot.
"""

from __future__ import annotations

import os

# the one fixed cache location when the environment names none: inside the
# checkout (git-ignored), because the cache key includes the path — a
# directory made from a temp name, a pid or a time never hits
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_compile_cache",
)


_cache_hit_listener_installed = False


def _install_compile_listeners() -> None:
    """Count program builds into telemetry: jax announces each
    cache-served compile, and the duration of every backend compile (a real
    build or a load from the persistent cache), via monitoring events; the
    listeners forward them to ``dllama_compile_cache_hits_total`` and
    ``dllama_compiles_total`` / ``dllama_compile_seconds_total`` (no-ops
    while telemetry is off)."""
    global _cache_hit_listener_installed
    from distributed_llama_tpu import telemetry

    telemetry.bind_compile_counters()  # at 0 from the first scrape on
    if _cache_hit_listener_installed:
        return
    from jax._src import monitoring

    def _on_event(event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            from distributed_llama_tpu import telemetry

            telemetry.note_compile_cache_hit()

    def _on_duration(event: str, duration: float, **kwargs) -> None:
        # fires for a real build and for a load from the persistent cache
        if event == "/jax/core/compile/backend_compile_duration":
            from distributed_llama_tpu import telemetry

            telemetry.note_compile(float(duration))

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _cache_hit_listener_installed = True


def enable_compilation_cache(cache_dir: str | None = None) -> str | None:
    """Turn on XLA's persistent compilation cache so a fresh process reuses
    compiled programs instead of re-compiling the model (a cold 32-layer
    Q40 7B prefill program compiles for tens of seconds).

    Called by every entry point before the first jit. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that directory
    and this function sets no other — neither the ``--compile-cache-dir``
    flag nor ``DLLAMA_COMPILE_CACHE`` overrides a cache placed from
    outside. Otherwise: the explicit argument (the flag), then
    ``DLLAMA_COMPILE_CACHE`` (empty string disables), else
    :data:`DEFAULT_COMPILE_CACHE`. Returns the directory in use, or None
    when disabled. With telemetry enabled, program builds are counted in
    ``dllama_compiles_total`` / ``dllama_compile_seconds_total`` and
    cache-served ones in ``dllama_compile_cache_hits_total``."""
    import jax

    _install_compile_listeners()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        if cache_dir is None:
            cache_dir = os.environ.get("DLLAMA_COMPILE_CACHE")
            if cache_dir == "":
                return None
        if cache_dir is None:
            cache_dir = DEFAULT_COMPILE_CACHE
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # default thresholds skip small programs and would also skip fast
    # RECOMPILES of big ones; cache everything that took >1s to build
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return placed or cache_dir
