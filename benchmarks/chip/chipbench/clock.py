"""Compile seconds and persistent-cache hits and misses, from JAX's own
monitoring events and its compiler log."""
from __future__ import annotations

import logging
import os

from chipbench.spec import ROOT

# the persistent compilation cache: in the checkout, at a fixed path (the
# path is part of each entry's key), whatever the environment names
CACHE_DIR = ROOT / ".jax_cache"


def use_checkout_cache(jax) -> None:
    """Cache every program the run compiles in :data:`CACHE_DIR`; the
    program's own entry points take it from the environment too."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileClock:
    """Counts XLA backend compiles, their seconds, and persistent-cache
    hits and misses (with the names of the modules that missed)."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.missed: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        # the miss is logged at DEBUG; take the records here and pass on
        # only what the logger would have shown anyway
        log = logging.getLogger("jax._src.compiler")
        self._shown = log.getEffectiveLevel()
        log.setLevel(logging.DEBUG)
        log.propagate = False
        log.addHandler(_MissLog(self.missed, self._shown))

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[float, int, int, int]:
        return self.seconds, self.compiles, self.cache_hits, len(self.missed)

    def since(self, snap) -> str:
        s, c, h, m = snap
        return (f"{self.compiles - c} compiles {self.seconds - s:.3f}s, "
                f"persistent cache hits {self.cache_hits - h}, "
                f"misses {len(self.missed) - m}"
                + (f" ({', '.join(self.missed[m:])})"
                   if len(self.missed) > m else ""))


class _MissLog(logging.Handler):
    """Keeps the module name of each persistent-cache miss."""

    def __init__(self, missed: list[str], shown: int):
        super().__init__(logging.DEBUG)
        self.missed = missed
        self.shown = shown

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("PERSISTENT COMPILATION CACHE MISS for"):
            self.missed.append(msg.split("'")[1])
        if record.levelno >= self.shown:
            logging.getLogger().handle(record)
