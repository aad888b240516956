"""Profiling hooks.

Parity target: reference ``modules/model/trainer/trainer.py:35-45``
(``time_profiler`` wall-time decorator on ``_train``/``_test``). Extended the
TPU way: a :class:`StepTimer` that accounts for XLA async dispatch (blocks on
ready before reading the clock) and an optional ``jax.profiler`` trace context
producing xplane dumps readable by TensorBoard/XProf.
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Optional

# The wall-time decorator now lives on the trace plane
# (metrics/trace.py): decorated units (`_train`/`_test`) emit the same log
# line AND a `cat="profile"` interval of the span plane, so their timing
# rides the unified observability timeline. Public name preserved.
from ..metrics.trace import time_profiler  # noqa: F401

logger = logging.getLogger(__name__)


class StepTimer:
    """Per-step timing that is honest under XLA's async dispatch.

    Calling ``stop(result)`` blocks on ``result`` being ready before reading the
    clock, so the measured interval covers actual device execution, not just
    Python dispatch. Keeps a running mean that skips the first ``warmup`` steps
    (compilation).
    """

    _warned_no_jax = False  # once per process, not once per timer

    def __init__(self, warmup: int = 1):
        self.warmup = warmup
        self.count = 0
        self.total = 0.0
        self._t0: Optional[float] = None

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self, result=None) -> float:
        if result is not None:
            # only a missing jax is survivable (host-only environments):
            # anything else — e.g. a typo'd result tree — must surface, not
            # silently degrade every measurement to dispatch-only timing
            try:
                import jax
            except ImportError:
                if not StepTimer._warned_no_jax:
                    StepTimer._warned_no_jax = True
                    logger.warning(
                        "StepTimer: jax unavailable; timings cover Python "
                        "dispatch only, not device execution."
                    )
            else:
                jax.block_until_ready(result)
        assert self._t0 is not None, "StepTimer.stop() without start()"
        dt = time.perf_counter() - self._t0
        self._t0 = None
        self.count += 1
        if self.count > self.warmup:
            self.total += dt
        return dt

    def mean(self) -> float:
        steady = self.count - self.warmup
        return self.total / steady if steady > 0 else 0.0


@contextlib.contextmanager
def device_trace(log_dir: Optional[str]):
    """``jax.profiler`` trace context; no-op when ``log_dir`` is None."""
    if log_dir is None:
        yield
        return
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        logger.info(f"Device trace written to {log_dir}.")
