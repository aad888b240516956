"""The traced stretch: ``jax.profiler`` with the Python tracer off (sixteen
loader threads of Python frames would drown the device events), the
benchmark's own window markers, and where the ``.xplane.pb`` landed."""

from __future__ import annotations

import glob
import os
import shutil
from pathlib import Path

from .trace_reduce import WINDOW_CLOSE, WINDOW_OPEN


def _mark(name: str) -> None:
    import jax

    with jax.profiler.TraceAnnotation(name):
        pass


def start(trace_dir: Path) -> None:
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    _mark(WINDOW_OPEN)


def stop(trace_dir: Path) -> str:
    """Close the window, stop the profiler, return the trace file."""
    import jax

    _mark(WINDOW_CLOSE)
    jax.profiler.stop_trace()
    found = sorted(glob.glob(
        str(trace_dir / "plugins" / "profile" / "*" / "*.xplane.pb")))
    if not found:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under {trace_dir}")
    return found[-1]


def annotation(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)
