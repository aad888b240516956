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
from pathlib import Path

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
    return cache_dir
