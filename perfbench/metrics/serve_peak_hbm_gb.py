"""``peak_hbm_gb`` in the serve cells: a name of its own because a per-layer
metric names the one end-to-end metric it moves."""

from .peak_hbm_gb import read  # noqa: F401
