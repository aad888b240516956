"""The sliding-window attention kernels' share of their roofline: the least
time the chip could take for the window layers' cores of the traced steps
(needed multiply-adds = the pairs the band permits, not the blocks the
kernels walk; bytes from the shapes, k/v once a key/value head:
``harness/flops_mellum2.py``) over the time the kernels took. FLOP-bound at
32/4 heads of 128."""

from ..harness.mellum2_trace import core_roofline_pct


def read(ctx):
    return core_roofline_pct(ctx, "sliding_attention", "window_kernels")
