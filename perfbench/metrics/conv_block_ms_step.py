"""Device self time per optimizer step and chip under the convolution
operators (module ``conv``): both projections and the gating and taps between
them, forward and backward."""

from ..harness.lfm2_trace import part_ms


def read(ctx):
    return part_ms(ctx, "conv", "short_conv")
