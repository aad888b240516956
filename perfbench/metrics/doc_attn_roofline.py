"""``attn_roofline`` in the cells whose rows are made from documents: a name of its
own because a per-layer metric names the one end-to-end metric it moves."""

from .attn_roofline import read  # noqa: F401
