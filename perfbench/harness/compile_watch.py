"""Compile accounting from ``jax.monitoring``: seconds spent obtaining
executables (a real compile on a persistent-cache miss, a read on a hit),
how many programs, hits and misses. A copy of the listeners in
``chip_smoke.py:_phase``. ``mark()`` / ``since(mark)`` count what happened
between two points: ``window_compiles`` is ``since(open)['programs']``."""

from __future__ import annotations

import logging


class CompileWatch:
    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.missed: list = []          # names of the programs that missed
        self._installed = False
        self._log = logging.getLogger("jax._src.compiler")
        self._saved_level = self._log.level
        watch = self

        class MissNames(logging.Filter):
            """jax names the module of each persistent-cache miss in a DEBUG
            record: note the name, keep DEBUG records off the handlers."""

            def filter(self, record):
                if record.levelno > logging.DEBUG:
                    return True
                if "CACHE MISS for" in str(record.msg) and record.args:
                    watch.missed.append(str(record.args[0]))
                return False

        self._filter = MissNames()

    def _on_duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.programs += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def install(self) -> "CompileWatch":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        self._log.addFilter(self._filter)
        self._log.setLevel(logging.DEBUG)
        self._installed = True
        return self

    def uninstall(self) -> None:
        if not self._installed:
            return
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
        self._log.removeFilter(self._filter)
        self._log.setLevel(self._saved_level)
        self._installed = False

    def __enter__(self) -> "CompileWatch":
        return self.install()

    def __exit__(self, *exc_info) -> None:
        self.uninstall()

    def mark(self) -> dict:
        return {"seconds": self.seconds, "programs": self.programs,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}

    def since(self, mark: dict) -> dict:
        now = self.mark()
        return {k: now[k] - mark[k] for k in now}
