"""Device time of the two-width causal attention kernels
(``ops/flash_causal.py``: ``%flash_causal_fwd`` / ``%flash_causal_bwd_*``)
per optimizer step and chip."""

from ..harness.joyai_trace import part_ms


def read(ctx):
    return part_ms(ctx, "causal_kernels")
