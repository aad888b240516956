"""``device_idle_pct`` in the serve cells: a name of its own because a per-layer
metric names the one end-to-end metric it moves."""

from .device_idle_pct import read  # noqa: F401
