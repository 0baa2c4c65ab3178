"""Compilations, counted and named: backend compiles and persistent-cache
loads through ``jax.monitoring``, and the name and shapes of every jitted
program lowered, from the debug record JAX writes before it compiles
(observed at DEBUG level by a filter that passes on only what the logger
passed before, so nothing more is printed and nothing changes)."""
from __future__ import annotations

import logging

_LOGGER = "jax._src.interpreters.pxla"


class _Names(logging.Filter):
    """Keeps the name and shapes of every program lowered; lets through only
    the records the logger would have passed at its former level."""

    def __init__(self, level: int):
        super().__init__()
        self.level = level
        self.names: list[str] = []

    def filter(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg[len("Compiling "):].split(". Argument")[0][:300])
        return record.levelno >= self.level


class CompileMeter:
    """Counts from the moment it is made; ``mark()`` then ``since(mark)``
    gives what happened in between."""

    def __init__(self):
        import jax

        self.compiles = self.cache_hits = 0
        log = logging.getLogger(_LOGGER)
        self._names = _Names(log.getEffectiveLevel())
        log.addFilter(self._names)
        log.setLevel(logging.DEBUG)

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @property
    def lowered(self) -> int:
        return len(self._names.names)

    def mark(self) -> tuple[int, int, int]:
        return self.compiles, self.cache_hits, self.lowered

    def since(self, mark: tuple[int, int, int]) -> dict:
        """Backend compiles, persistent-cache loads and programs lowered after
        ``mark``, with the lowered programs' names and shapes."""
        return {"compiles": self.compiles - mark[0],
                "cache_loads": self.cache_hits - mark[1],
                "lowered": self.lowered - mark[2],
                "names": self._names.names[mark[2]:]}
