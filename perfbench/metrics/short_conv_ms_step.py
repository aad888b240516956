"""Device self time per optimizer step and chip under scope ``short_conv``:
the double gating and the taps of every convolution operator, forward and
backward, whatever implements them."""

from ..harness.lfm2_trace import part_ms


def read(ctx):
    return part_ms(ctx, "short_conv")
