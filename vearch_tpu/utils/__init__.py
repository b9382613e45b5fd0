import os
import time

# Span epochs are derived from monotonic measurements plus this
# process-constant anchor: durations must survive wall-clock steps
# (lint VL203), and a later NTP step merely shifts where spans sit on
# the collector's absolute timeline. Shared by every module whose
# timestamps cross function boundaries before span emission (engine
# phases, ivf dispatch capture, microbatch queue waits).
MONO_EPOCH_OFFSET = time.time() - time.monotonic()  # lint: allow[wall-clock] span epoch anchor, captured once at import


def mono_us(t_monotonic: float) -> int:
    """Monotonic seconds -> wall-anchored epoch microseconds, the
    `start_us` convention of the tracing layer."""
    return int((MONO_EPOCH_OFFSET + t_monotonic) * 1e6)


#: where the persistent compilation cache lives when the environment
#: does not place it: ONE fixed path inside the checkout (git-ignored).
#: The path is part of the cache key, so a temporary, per-pid or
#: per-run directory would never hit.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    The one place the program decides where compiled programs persist.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets NO directory in code; where it is not, the cache goes to
    :data:`DEFAULT_COMPILE_CACHE_DIR`. Either way every program is
    cached, including the small search programs whose compile time sits
    below JAX's default 1 s threshold. Idempotent; call it before the
    first compile (servers and chip_smoke.py do at start-up).
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def prune_job_registry(jobs: dict, keep: int = 64) -> None:
    """Age out completed job records oldest-first, keeping `keep`
    finished entries (shared by the master and PS async-backup
    registries; caller holds the registry lock)."""
    done = [k for k in sorted(jobs, key=lambda k: jobs[k]["updated"])
            if jobs[k]["status"] in ("done", "error")]
    for old in done[:-keep]:
        del jobs[old]
