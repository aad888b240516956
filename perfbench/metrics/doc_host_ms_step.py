"""``host_ms_step`` in the cells whose rows are made from documents: a name of its
own because a per-layer metric names the one end-to-end metric it moves."""

from .host_ms_step import read  # noqa: F401
