"""``mfu_pct`` in the cells whose rows are made from documents: a name of its
own because a per-layer metric names the one end-to-end metric it moves."""

from .mfu_pct import read  # noqa: F401
