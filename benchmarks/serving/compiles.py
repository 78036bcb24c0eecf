"""Compile accounting through ``jax.monitoring`` and the Pallas kernels in
a compiled program."""
from __future__ import annotations

import os
import pathlib

import jax

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Counter:
    """Backend compiles (with their seconds) and persistent-cache hits
    since the process registered it.  A program that is loaded from the
    cache counts as a hit and not as a compile."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.compiles += 1
            self.compile_s += seconds

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits}


def enable_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else the fixed directory ``<root>/.jax_cache`` (the
    path is part of what makes a second run find the first run's
    programs).  Every program is cached, however short its compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def kernel_counts(hlo_text: str) -> dict[str, int]:
    """Pallas kernels in a compiled program, by kernel name."""
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = line.split("/pallas_call")[0].rsplit("/", 1)[-1]
        counts[name] = counts.get(name, 0) + 1
    return counts
