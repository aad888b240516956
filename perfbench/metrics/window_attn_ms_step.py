"""Device time of the sliding-window attention kernels
(``ops/flash_window.py``: ``%flash_window_fwd`` / ``%flash_window_bwd*``) per
optimizer step and chip. The sum of a group's dk and dv after the backward
call is XLA's and is not in it."""

from ..harness.mellum2_trace import part_ms


def read(ctx):
    return part_ms(ctx, "window_kernels")
