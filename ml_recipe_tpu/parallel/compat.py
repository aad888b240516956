"""One import point for ``shard_map`` (ring attention, the pipeline island,
tests) so every caller uses the same spelling."""

from __future__ import annotations

import jax

__all__ = ["shard_map"]

shard_map = jax.shard_map
