"""Process start-up shared by every entry point.

Two things every ``main()`` does before its first computation, so that any
log says where it ran and no run recompiles what an earlier run compiled:

- :func:`enable_compile_cache` turns on JAX's persistent compilation cache.
  Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is used and
  nothing here names another; where it is not, the cache lives at one fixed
  path inside the checkout (``.jax_cache/``, git-ignored). The path is part
  of what a later run has to find again, so it is never derived from a pid,
  a timestamp or a ``tempfile`` name. Launcher children inherit the
  environment and resolve the same fixed path, so a world shares one cache.
- :func:`announce_devices` prints one line naming ``platform``,
  ``device_kind`` and the device count; :func:`require_tpu` turns "asked for
  the chip and did not get it" into an error instead of a CPU run under a
  TPU name.
"""

from __future__ import annotations

import os
import sys

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the one in-checkout cache location (used only when CACHE_ENV is unset)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

# jax.monitoring listeners are process-global and cannot be scoped to an
# object, so the counters they feed are module state as well
_cache_counts = {"requests": 0, "hits": 0}
_listening = False


def _say(line: str) -> None:
    """One line, one write: the ranks of a launched world share a pipe, and
    a line written in pieces comes out spliced into a neighbour's."""
    sys.stdout.write(line + "\n")
    sys.stdout.flush()


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        _cache_counts["requests"] += 1
    elif event == "/jax/compilation_cache/cache_hits":
        _cache_counts["hits"] += 1


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Call before the process's first compilation (JAX binds the cache on
    first use). Every program is cached, however quick its compile, where
    JAX's default keeps only those that took a second or more: most of a
    run's programs are sub-second ones, every rank of a launched world
    compiles the same ones, and a warm run is then one that compiles nothing
    (``compiled == 0`` in :func:`compile_cache_stats`). The directory grows
    by one file for each distinct program and is safe to delete.
    ``jax_compilation_cache_max_size`` is left alone on purpose: on jax
    0.9.0 its eviction pass raises on any entry written by a process that
    did not set it, after which every write fails with only a warning.
    """
    global _listening
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return jax.config.jax_compilation_cache_dir


def compile_cache_stats() -> dict:
    """``{"requests", "hits", "compiled"}`` since :func:`enable_compile_cache`:
    compile requests that consulted the cache, those it answered, and the
    remainder that ran the compiler."""
    requests, hits = _cache_counts["requests"], _cache_counts["hits"]
    return {"requests": requests, "hits": hits, "compiled": requests - hits}


def report_compile_cache(role: str) -> dict:
    """Print ``role``'s compile-cache line (where, and how much of the run's
    compiling the cache answered); returns the stats."""
    st = compile_cache_stats()
    _say(f"{role}: compile cache {jax.config.jax_compilation_cache_dir} "
         f"requests={st['requests']} hits={st['hits']} "
         f"compiled={st['compiled']}")
    return st


def device_summary() -> dict:
    """The default backend as JAX reports it."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def announce_devices(role: str) -> dict:
    """Print the one line that says where ``role`` runs; returns the summary."""
    d = device_summary()
    # a launcher that narrowed this process to one chip of the host: say which
    chips = os.environ.get("TPU_VISIBLE_CHIPS")
    _say(f"{role}: platform={d['platform']} device_kind={d['kind']!r} "
         f"devices={d['count']}"
         + (f" TPU_VISIBLE_CHIPS={chips}"
            if chips and d["platform"] == "tpu" else ""))
    return d


def require_tpu(what: str) -> None:
    """Raise unless the default backend is a TPU — for callers that asked
    for the chip (``--backend tpu``, the benchmarks, ``chip_smoke.py``)."""
    platform = jax.default_backend()
    if platform != "tpu":
        raise RuntimeError(
            f"{what} needs a TPU but JAX's default backend is {platform!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}); "
            "refusing to carry on on another device under a TPU name")
