"""Compile-cache placement shared by every entry point.

JAX's persistent compilation cache is the one compile cache of the default
path (the AOT program store of ``ops/aot.py`` is opt-in). Its directory is
part of every cache key's environment, so it must not move between runs:
``configure_compile_cache`` leaves an operator-chosen
``JAX_COMPILATION_CACHE_DIR`` alone and otherwise pins one fixed path inside
the checkout — never a temporary name, pid, uid or time.
"""

from __future__ import annotations

import os
import threading
import time
from pathlib import Path

from ..metrics import trace

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache (git-ignored)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, jax reads the directory from the
    environment itself and no directory is set in code. The thresholds drop
    to zero either way, so the sub-second Pallas probe compiles are cached
    next to the whole-step programs. Call first in every entry point,
    before anything compiles.
    """
    import jax

    cache_dir = os.environ.get(ENV_CACHE_DIR)
    if not cache_dir:
        cache_dir = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # A Pallas kernel's body is serialized into its program WITH debug
    # locations, and the cache key hashes those bytes. With full Python
    # tracebacks in the locations, a kernel whose inner jitted helpers
    # (jnp.where ...) were first traced under the autotuner's compile probe
    # carries the probe's call path: a cold start (probes) and the next start
    # (verdicts cached, no probes) lower to different bytes, and every
    # kernel-bearing program misses the cache once more (seen on the chip,
    # PR 21). One frame per location is the same on both paths. The frame
    # limit gives that one frame and keeps an operation's whole scope path
    # ("jit(train_step)/optimizer/add") in the HLO's op_name; switching
    # jax_include_full_tracebacks_in_locations off, as PR 21 did, gave the
    # same frame but left XLA "add" alone, and the scope map
    # (metrics/trace.py) nothing to join by (PR 24).
    jax.config.update("jax_traceback_in_locations_limit", 1)
    record_compile_spans()
    return cache_dir


# jax.monitoring's three stages of obtaining an executable, as the span plane
# names them. Tracing and lowering are paid on every start; the third is a
# compile on a persistent-cache miss and a read on a hit.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_STAGES = {
    TRACE_EVENT: "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_recording = False
_tracing = threading.local()    # .depth: the traces open on this thread


def _on_stage_begin(event: str, value: float, **said) -> None:
    # jax says when a stage begins, too (a scalar: its wall-clock start)
    if event == TRACE_EVENT:
        _tracing.depth = getattr(_tracing, "depth", 0) + 1


def _on_compile_stage(event: str, seconds: float, **said) -> None:
    stage = COMPILE_STAGES.get(event)
    if stage is None:
        return
    if stage == "trace":
        # a step's trace holds tens of thousands of inner ones (every jitted
        # helper it calls): the outermost's record covers them all
        _tracing.depth = max(getattr(_tracing, "depth", 1) - 1, 0)
        if _tracing.depth:
            return
    now = time.perf_counter()   # jax reports as the stage ends
    trace.complete(stage, now - seconds, now, cat="compile",
                   args={"fun": said.get("fun_name")})


def record_compile_spans() -> None:
    """The program's one ``jax.monitoring`` duration listener (and the
    scalar listener that tells it how deep a trace is nested): each stage
    becomes a ``cat="compile"`` record of the span plane
    (``metrics/trace.py``), named by the function it served. Registered once
    a process, by ``configure_compile_cache``."""
    global _recording
    if not _recording:
        import jax

        jax.monitoring.register_scalar_listener(_on_stage_begin)
        jax.monitoring.register_event_duration_secs_listener(_on_compile_stage)
        _recording = True
