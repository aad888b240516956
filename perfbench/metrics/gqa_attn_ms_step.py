"""Device time of the causal attention kernels at grouped-query heads
(``ops/flash_causal.py``: ``%flash_causal_fwd`` / ``%flash_causal_bwd*``) per
optimizer step and chip. The sum of a group's dk and dv after the backward
call is XLA's and is not in it."""

from ..harness.lfm2_trace import part_ms


def read(ctx):
    return part_ms(ctx, "causal_kernels")
