"""The persistent XLA compile cache: one policy, one function.

The crypto kernels are deep programs whose compiles dominate cold wall
time, so every entry point (the CLI, the verify service, bench, the
tools, tests, chip_smoke.py's children) calls
`configure_compile_cache()` before its first compile and all of them
share the same entries. The directory is `JAX_COMPILATION_CACHE_DIR`
when the environment sets it — then nothing else is ever written — and
`<checkout>/.jax_cache/<host_tag>` otherwise: a fixed path, because an
entry one process wrote is only found by the next if both look in the
same place.

`compile_log()` is the read side: what this process compiled, how long
each program took, and how often the persistent cache answered — the
verify service ships it in its dump and the node logs it after the
startup warm, which is how a cold start's cost is attributed to
programs instead of guessed.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def repo_root() -> str:
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def cache_dir() -> str:
    """Where the compile cache lives. Without the env override the dir
    is per host ISA: XLA:CPU AOT entries embed host-specific
    instructions (the loader itself warns 'could lead to execution
    errors such as SIGILL' on feature mismatch — and a stale cross-host
    entry segfaulted a real test run), so it is keyed by the same CPU
    fingerprint the native .so builds use. A function of the machine
    only — never of a pid, a time or a temp name."""
    override = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if override:
        return override
    from ..crypto._native_build import _host_tag

    return os.path.join(repo_root(), ".jax_cache", _host_tag())


def configure_compile_cache() -> str:
    """Point jax's persistent cache at `cache_dir()`; returns the dir.
    Applied through jax.config.update, so it works whether or not jax
    was imported first — only the first COMPILE has to come after."""
    import jax

    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileLog:
    """What this process asked XLA to compile, from jax.monitoring.

    `programs` maps a jitted function's name to [count, seconds]:
    seconds cover compile-or-load, so a persistent-cache hit shows up
    as a count, the seconds its load took (not near zero for the deep
    programs on a TPU) and one `cache_hits` tick."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.programs: dict[str, list] = {}
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        if event != _BACKEND_COMPILE_EVENT:
            return
        name = str(kw.get("fun_name", "?"))
        with self._lock:
            row = self.programs.setdefault(name, [0, 0.0])
            row[0] += 1
            row[1] += float(seconds)

    def _on_event(self, event: str, **kw) -> None:
        if event == _CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1
        elif event == _CACHE_MISS_EVENT:
            with self._lock:
                self.cache_misses += 1

    def totals(self) -> dict:
        with self._lock:
            return {
                "compilations": sum(c for c, _ in self.programs.values()),
                "seconds": round(
                    sum(s for _, s in self.programs.values()), 3
                ),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
            }

    def snapshot(self) -> dict:
        """The totals plus the per-program rows."""
        out = self.totals()
        with self._lock:
            out["programs"] = {
                name: {"count": c, "seconds": round(s, 3)}
                for name, (c, s) in sorted(self.programs.items())
            }
        return out


_compile_log: Optional[CompileLog] = None
_compile_log_lock = threading.Lock()


def compile_log() -> CompileLog:
    """The process-wide log, registered with jax.monitoring on first use
    (listeners cannot be removed, so there is exactly one)."""
    global _compile_log
    with _compile_log_lock:
        if _compile_log is None:
            import jax.monitoring

            log = CompileLog()
            jax.monitoring.register_event_duration_secs_listener(
                log._on_duration
            )
            jax.monitoring.register_event_listener(log._on_event)
            _compile_log = log
        return _compile_log
