import os
import time

# Span epochs are derived from monotonic measurements plus this
# process-constant anchor: durations must survive wall-clock steps
# (lint VL203), and a later NTP step merely shifts where spans sit on
# the collector's absolute timeline. THE one conversion from the
# monotonic clock to the epoch in the span path: spans keep
# time.monotonic_ns() stamps and become epoch microseconds only when
# they are read (cluster/tracing.py), and the engine's phase rows
# (`[name, start_us, dur_us]`) go out and come back through it.
MONO_EPOCH_OFFSET_NS = time.time_ns() - time.monotonic_ns()  # lint: allow[wall-clock] span epoch anchor, captured once at import


def mono_ns_to_epoch_us(t_ns: int) -> int:
    """time.monotonic_ns() stamp -> wall-anchored epoch microseconds."""
    return (t_ns + MONO_EPOCH_OFFSET_NS) // 1000


def epoch_us_to_mono_ns(start_us: int) -> int:
    """Inverse of mono_ns_to_epoch_us, to microsecond resolution."""
    return start_us * 1000 - MONO_EPOCH_OFFSET_NS


def mono_us(t_monotonic: float) -> int:
    """Monotonic seconds -> wall-anchored epoch microseconds, the
    `start_us` convention of the tracing layer."""
    return mono_ns_to_epoch_us(int(t_monotonic * 1e9))


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
