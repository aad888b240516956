"""Device-memory byte arithmetic shared by the HBM pre-flight planners.

One definition of "projected per-device bytes" for both planners:
``Trainer.preflight_train_step`` (raises ``batch_split`` instead of an XLA
train-step OOM) and ``QAEngine.preflight_predict_step`` (shrinks the
serving bucket grid instead of OOMing mid-traffic). Lives in utils so the
serving request path does not import the training stack.
"""

from __future__ import annotations

import logging
from typing import Optional

import jax

logger = logging.getLogger(__name__)


def device_hbm_bytes() -> Optional[int]:
    """Per-device HBM capacity in bytes, or ``None`` when the backend does
    not report one — the pre-flight planner then stands down rather than
    guessing. That is the normal case on the CPU backend; on a TPU it means
    an over-committed configuration will meet XLA's allocator unplanned, so
    it is a warning there."""
    device = jax.local_devices()[0]
    stats = device.memory_stats()
    limit = (stats or {}).get("bytes_limit")
    if not limit:
        if device.platform == "tpu":
            logger.warning(
                "HBM pre-flight: %s reports no bytes_limit in memory_stats(); "
                "the planner stands down.", device,
            )
        return None
    return int(limit)


def preflight_bytes(memory_analysis) -> Optional[int]:
    """Projected per-device HBM requirement of a compiled step: arguments +
    outputs + temporaries, minus the donated-buffer aliasing (donated
    inputs' output copies reuse the argument buffers). ``None`` when the
    analysis is unavailable or malformed — the planner then stands down
    instead of acting on garbage."""
    if memory_analysis is None:
        return None
    try:
        need = (
            int(memory_analysis.argument_size_in_bytes)
            + int(memory_analysis.output_size_in_bytes)
            + int(memory_analysis.temp_size_in_bytes)
            - int(getattr(memory_analysis, "alias_size_in_bytes", 0))
        )
    except (AttributeError, TypeError, ValueError):
        return None
    return need if need > 0 else None
